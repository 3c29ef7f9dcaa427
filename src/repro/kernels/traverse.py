"""Pallas TPU kernel: batched learned-index GET (traversal + leaf probe).

TPU mapping of the DPA traverser (DESIGN.md Sec 2):

  * grid dimension 0 tiles the request wave — one grid program plays the
    role of a group of DPA threads working a burst of packets;
  * the index pools (inner nodes, pivot slots, leaf metadata) are placed in
    **VMEM** via untiled BlockSpecs — the analogue of the NIC-side "DPA
    memory" tier.  This imposes the same design pressure as the paper's
    1 GiB DPA memory: the *index* must stay small, which is exactly why the
    values live elsewhere;
  * the leaf key/value arrays and the per-leaf insert buffers live in
    ``memory_space=ANY`` (compiler-placed, HBM for real sizes) — the "host
    memory behind DMA" tier.  Each lane issues an explicit bounded window
    copy (``pl.load`` with a dynamic slice) for its eps_leaf window and its
    value — one "DMA" per touch, mirroring the paper's two PCIe crossings
    per GET;
  * inner-node routing is vectorised across the tile (gathers from VMEM),
    because unlike the DPA's scalar RISC-V threads the VPU is 8x128 wide —
    this is the hardware adaptation: same memory placement, lane-parallel
    execution.

The pure-jnp oracle is ``repro.core.lookup.get_batch`` (re-exported through
``ref.py``); tests sweep shapes and assert exact equality in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ANY = pl.ANY  # compiler-placed memory (HBM at real sizes)


def _limb_le(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def _limb_eq(a_hi, a_lo, b_hi, b_lo):
    return (a_hi == b_hi) & (a_lo == b_lo)


def _delta_f32(a_hi, a_lo, b_hi, b_lo):
    borrow = (a_lo < b_lo).astype(jnp.uint32)
    lo = a_lo - b_lo
    hi = a_hi - b_hi - borrow
    return hi.astype(jnp.float32) * jnp.float32(4294967296.0) + lo.astype(jnp.float32)


def _get_kernel(
    # index pools (VMEM — "DPA memory")
    nsf_ref,  # (Ni, 7, 2) node_seg_first
    nsl_ref,  # (Ni, 7) node_seg_slope
    nsc_ref,  # (Ni, 7) node_seg_count
    nss_ref,  # (Ni, 7) node_seg_slot
    pk_ref,  # (Np, 128, 2) pivot_keys
    pc_ref,  # (Np, 128) pivot_child
    la_ref,  # (Nl, 2) leaf_anchor
    ls_ref,  # (Nl,) leaf_slope
    lc_ref,  # (Nl,) leaf_count
    lslot_ref,  # (Nl,) leaf_slot
    root_ref,  # (1,) root node id
    # big-memory pools (ANY — "host memory behind DMA")
    hk_ref,  # (Ns, 128, 2) hbm_keys
    hv_ref,  # (Ns, 128, 2) hbm_vals
    ibk_ref,  # (Nl, cap, 2)
    ibv_ref,  # (Nl, cap, 2)
    ibo_ref,  # (Nl, cap)
    ibc_ref,  # (Nl,)
    # request tile
    khi_ref,  # (Bt,)
    klo_ref,  # (Bt,)
    # outputs
    vhi_ref,
    vlo_ref,
    found_ref,
    *,
    depth: int,
    eps_inner: int,
    eps_leaf: int,
):
    khi = khi_ref[...]
    klo = klo_ref[...]
    bt = khi.shape[0]

    # ---- inner descent: vectorised VMEM gathers ---------------------------
    node = jnp.full((bt,), root_ref[0], dtype=jnp.int32)
    w_in = 2 * eps_inner + 2
    for _ in range(depth - 1):
        sf = jnp.take(nsf_ref[...], node, axis=0)  # (Bt, 7, 2)
        le = _limb_le(sf[:, :, 0], sf[:, :, 1], khi[:, None], klo[:, None])
        seg = jnp.maximum(jnp.sum(le[:, 1:].astype(jnp.int32), axis=1), 0)
        bidx = jnp.arange(bt)
        a_hi = sf[bidx, seg, 0]
        a_lo = sf[bidx, seg, 1]
        below = ~_limb_le(a_hi, a_lo, khi, klo)
        delta = _delta_f32(khi, klo, a_hi, a_lo)
        slope = jnp.take(nsl_ref[...], node, axis=0)[bidx, seg]
        count = jnp.take(nsc_ref[...], node, axis=0)[bidx, seg]
        slot = jnp.take(nss_ref[...], node, axis=0)[bidx, seg]
        pred = jnp.where(below, 0.0, slope * delta)
        lo = jnp.clip(
            jnp.floor(pred).astype(jnp.int32) - eps_inner,
            0,
            jnp.maximum(count - w_in, 0),
        )
        rows = jnp.take(pk_ref[...], slot, axis=0)  # (Bt, 128, 2)
        idx = lo[:, None] + jnp.arange(w_in, dtype=jnp.int32)[None, :]
        wk = jnp.take_along_axis(rows, idx[:, :, None], axis=1)
        lemask = _limb_le(wk[:, :, 0], wk[:, :, 1], khi[:, None], klo[:, None])
        inr = idx < count[:, None]
        rank = jnp.maximum(
            lo + jnp.sum((lemask & inr).astype(jnp.int32), axis=1) - 1, 0
        )
        crow = jnp.take(pc_ref[...], slot, axis=0)
        node = jnp.take_along_axis(crow, rank[:, None], axis=1)[:, 0]

    leaf = node

    # ---- leaf model (VMEM) -------------------------------------------------
    anch = jnp.take(la_ref[...], leaf, axis=0)  # (Bt, 2)
    below = ~_limb_le(anch[:, 0], anch[:, 1], khi, klo)
    delta = _delta_f32(khi, klo, anch[:, 0], anch[:, 1])
    pred = jnp.where(below, 0.0, jnp.take(ls_ref[...], leaf, axis=0) * delta)
    count = jnp.take(lc_ref[...], leaf, axis=0)
    slot = jnp.take(lslot_ref[...], leaf, axis=0)
    w_lf = 2 * eps_leaf + 2
    win_lo = jnp.clip(
        jnp.floor(pred).astype(jnp.int32) - eps_leaf,
        0,
        jnp.maximum(count - w_lf, 0),
    )

    # ---- per-lane "DMA" loop against the host-memory tier -----------------
    def lane(i, carry):
        vhi, vlo, found = carry
        sl = slot[i]
        lo_i = win_lo[i]
        # one bounded window copy (the paper's contiguous-keys DMA)
        wk = hk_ref[pl.ds(sl, 1), pl.ds(lo_i, w_lf), slice(None)][0]
        le = _limb_le(wk[:, 0], wk[:, 1], khi[i], klo[i])
        inr = (lo_i + jnp.arange(w_lf, dtype=jnp.int32)) < count[i]
        rank = lo_i + jnp.sum((le & inr).astype(jnp.int32)) - 1
        safe = jnp.maximum(rank, 0)
        kk = hk_ref[pl.ds(sl, 1), pl.ds(safe, 1), slice(None)][0, 0]
        hit_tree = (rank >= 0) & _limb_eq(kk[0], kk[1], khi[i], klo[i])
        # second DMA: the value
        vv = hv_ref[pl.ds(sl, 1), pl.ds(safe, 1), slice(None)][0, 0]
        # insert buffer (prefetched alongside in the paper; newest wins)
        lf = leaf[i]
        bk = ibk_ref[pl.ds(lf, 1), slice(None), slice(None)][0]
        bv = ibv_ref[pl.ds(lf, 1), slice(None), slice(None)][0]
        bo = ibo_ref[pl.ds(lf, 1), slice(None)][0]
        bc = ibc_ref[pl.ds(lf, 1),][0]
        cap = bk.shape[0]
        pos = jnp.arange(cap, dtype=jnp.int32)
        m = _limb_eq(bk[:, 0], bk[:, 1], khi[i], klo[i]) & (pos < bc) & (bo != 0)
        newest = jnp.max(jnp.where(m, pos, -1))
        has = newest >= 0
        safe_b = jnp.maximum(newest, 0)
        is_put = has & (bo[safe_b] == 1)
        is_del = has & (bo[safe_b] == 2)
        ok = is_put | (hit_tree & ~is_del)
        out_hi = jnp.where(is_put, bv[safe_b, 0], vv[0])
        out_lo = jnp.where(is_put, bv[safe_b, 1], vv[1])
        vhi = vhi.at[i].set(jnp.where(ok, out_hi, 0))
        vlo = vlo.at[i].set(jnp.where(ok, out_lo, 0))
        found = found.at[i].set(ok.astype(jnp.int32))
        return vhi, vlo, found

    vhi0 = jnp.zeros((bt,), dtype=jnp.uint32)
    vlo0 = jnp.zeros((bt,), dtype=jnp.uint32)
    fnd0 = jnp.zeros((bt,), dtype=jnp.int32)
    vhi, vlo, found = jax.lax.fori_loop(0, bt, lane, (vhi0, vlo0, fnd0))
    vhi_ref[...] = vhi
    vlo_ref[...] = vlo
    found_ref[...] = found


def get_pallas(
    tree,
    ib,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    depth: int,
    eps_inner: int,
    eps_leaf: int,
    block_requests: int = 128,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """pallas_call wrapper over the GET kernel.  Returns (vhi, vlo, found).

    Not on the served path, and it does not lower for TPU yet: Mosaic
    refuses its 3-D gathers ("Only 2D gather is supported";
    ``tests/test_tpu_compile.py`` pins it), and its whole-pool VMEM blocks
    would not fit v5e's VMEM at 50M keys (``pivot_keys`` alone is ~200 MB).
    ``interpret=True`` runs the body in the Pallas interpreter, which is how
    the equivalence tests use it; the served GET is ``core.lookup.get_batch``.
    """
    B = khi.shape[0]
    assert B % block_requests == 0, "pad the wave to the request tile"
    grid = (B // block_requests,)

    def tile(i):
        return (i,)

    def whole(i):
        return tuple([0] * 1)

    kernel = functools.partial(
        _get_kernel, depth=depth, eps_inner=eps_inner, eps_leaf=eps_leaf
    )
    vmem = lambda arr: pl.BlockSpec(
        arr.shape, lambda i: tuple([0] * arr.ndim)
    )
    anymem = lambda arr: pl.BlockSpec(
        arr.shape, lambda i: tuple([0] * arr.ndim), memory_space=ANY
    )
    root_arr = jnp.reshape(tree.root, (1,))
    in_specs = [
        vmem(tree.node_seg_first),
        vmem(tree.node_seg_slope),
        vmem(tree.node_seg_count),
        vmem(tree.node_seg_slot),
        vmem(tree.pivot_keys),
        vmem(tree.pivot_child),
        vmem(tree.leaf_anchor),
        vmem(tree.leaf_slope),
        vmem(tree.leaf_count),
        vmem(tree.leaf_slot),
        vmem(root_arr),
        anymem(tree.hbm_keys),
        anymem(tree.hbm_vals),
        anymem(ib.keys),
        anymem(ib.vals),
        anymem(ib.op),
        anymem(ib.count),
        pl.BlockSpec((block_requests,), lambda i: (i,)),
        pl.BlockSpec((block_requests,), lambda i: (i,)),
    ]
    out_specs = [
        pl.BlockSpec((block_requests,), lambda i: (i,)),
        pl.BlockSpec((block_requests,), lambda i: (i,)),
        pl.BlockSpec((block_requests,), lambda i: (i,)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B,), jnp.uint32),
        jax.ShapeDtypeStruct((B,), jnp.uint32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
    ]
    vhi, vlo, found = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(
        tree.node_seg_first,
        tree.node_seg_slope,
        tree.node_seg_count,
        tree.node_seg_slot,
        tree.pivot_keys,
        tree.pivot_child,
        tree.leaf_anchor,
        tree.leaf_slope,
        tree.leaf_count,
        tree.leaf_slot,
        root_arr,
        tree.hbm_keys,
        tree.hbm_vals,
        ib.keys,
        ib.vals,
        ib.op,
        ib.count,
        khi,
        klo,
    )
    return vhi, vlo, found.astype(bool)
