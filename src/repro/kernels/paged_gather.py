"""Pallas kernel: gather KV blocks by page-table slot list.

The serving-side sibling of the range-scan kernel: the learned page table
(RANGE over the DPA-Store index) yields an ordered slot list; this kernel
streams the listed blocks out of the big HBM pool into a contiguous
(S, H, hd) buffer for attention.  Grid = one program per block; the output
BlockSpec tiles the destination, the pool stays in ``memory_space=ANY`` and
each program issues one whole-block dynamic copy — the paper's sequential
leaf DMA, sized to a KV block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .traverse import ANY


def _gather_kernel(slots_ref, pool_ref, out_ref):
    i = pl.program_id(0)
    slot = slots_ref[i]
    out_ref[0, :, :, :] = pool_ref[pl.ds(slot, 1), :, :, :][0]


def gather_pallas(
    pool: jnp.ndarray,  # (N, bs, H, hd)
    slots: jnp.ndarray,  # (n,) i32
    *,
    interpret: bool,
) -> jnp.ndarray:
    n = slots.shape[0]
    _, bs, H, hd = pool.shape
    return pl.pallas_call(
        _gather_kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(slots.shape, lambda i: (0,)),
            pl.BlockSpec(pool.shape, lambda i: (0, 0, 0, 0), memory_space=ANY),
        ],
        out_specs=pl.BlockSpec((1, bs, H, hd), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, bs, H, hd), pool.dtype),
        interpret=interpret,
    )(slots, pool)


def gather_ref(pool: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    return pool[slots]


def gather(pool, slots, *, impl: str):
    """``impl``: ``"ref"``, ``"pallas"`` or ``"pallas_interpret"`` — the
    caller names the implementation; nothing is chosen by backend."""
    if impl not in ("ref", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    if slots.shape[0] == 0:
        return jnp.zeros((0,) + pool.shape[1:], pool.dtype)
    if impl == "ref":
        return gather_ref(pool, slots)
    return gather_pallas(pool, slots, interpret=(impl == "pallas_interpret"))
