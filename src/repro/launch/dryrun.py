import os

os.environ["JAX_PLATFORMS"] = "cpu"
_HOSTS = "--xla_force_host_platform_device_count=512"
if _HOSTS not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_HOSTS}".strip()
# The lines above MUST run before any other import: jax locks the platform
# and device count at first initialisation.  This module is the ONLY place
# the 512 placeholder host devices exist — tests and benches see the real
# device.  The dry run always compiles on the CPU: on a machine with a chip,
# a second process must not claim it.

"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

For each live cell this builds the exact production computation
(train_step / prefill / decode_step) with the baseline sharding rules,
lowers against ShapeDtypeStruct stand-ins (zero allocation), compiles for
the 256-chip single-pod and 512-chip two-pod meshes, and records:

  * memory_analysis()  — per-device argument/output/temp bytes (fits HBM?)
  * cost_analysis()    — HLO FLOPs + bytes accessed (roofline numerator)
  * collective bytes   — parsed from the post-SPMD HLO text per op kind

Results land in benchmarks/results/dryrun/<cell>.json; EXPERIMENTS.md's
§Dry-run and §Roofline tables are generated from those files.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cell_supported
from repro.configs.base import ArchConfig, ShapeConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.training import optimizer, train_step as ts

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

_COLL_RE = re.compile(
    r"(\w+)\[([\d,]*)\]\S*\s+(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def parse_collectives(hlo_text: str):
    """Per-device collective output bytes by op kind (post-SPMD HLO)."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, op = m.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.setdefault(op, [0, 0])
        out[op][0] += 1
        out[op][1] += n * _BYTES.get(dt, 4)
    return {k: {"count": v[0], "bytes": v[1]} for k, v in out.items()}


def input_specs(cfg: ArchConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train" or (shape.kind == "prefill" and True):
        if cfg.frontend != "none":
            return {
                "tokens": None,
                "embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "embeds": None,
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
    # decode: one new token against a seq_len cache
    if cfg.frontend != "none":
        tok = jax.ShapeDtypeStruct((B, cfg.d_model), jnp.bfloat16)
    else:
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    return {"token": tok, "pos": jax.ShapeDtypeStruct((), jnp.int32)}


def microbatches_for(cfg: ArchConfig, shape: ShapeConfig) -> int:
    """Keep per-microbatch tokens/device ~<= 8k on the big archs."""
    total, _ = cfg.param_counts()
    if total > 1e11:
        return 16
    if total > 2e10:
        return 4
    return 1


def _act_shardings(cfg, shape, mesh, kind):
    ba = shd.batch_axes(mesh)
    out = {}
    if kind in ("train", "prefill"):
        out["residual"] = NamedSharding(
            mesh, P(ba, shd._maybe("model", shape.seq_len, mesh), None)
        )
        out["layer_input"] = NamedSharding(mesh, P(ba, None, None))
        out["logits"] = NamedSharding(
            mesh, P(ba, None, shd._maybe("model", cfg.vocab_size, mesh))
        )
    return out


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    cfg_override=None,
    mb_override=None,
    perf: tuple = (),
):
    cfg = cfg_override or ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = shape.kind
    lm.set_activation_shardings(_act_shardings(cfg, shape, mesh, kind))
    # §Perf hooks (see EXPERIMENTS.md §Perf); default off = baseline
    from repro.models import layers as _layers

    flags = {k: True for k in perf}
    if "cp_decode" in perf and kind == "decode":
        flags["decode_logits_shard"] = NamedSharding(
            mesh, P(None, None, None, None, "data")
        )
        flags.pop("cp_decode")
    for f in list(flags):
        if f.startswith("block_kv="):
            flags.pop(f)
            flags["block_kv"] = int(f.split("=")[1])
    if "attn_pin" in perf:
        ba = shd.batch_axes(mesh)
        g_ax = shd._maybe(
            "model", cfg.n_heads // max(cfg.n_kv_heads, 1), mesh
        )
        flags.pop("attn_pin")
        flags["attn_q_shard"] = NamedSharding(
            mesh, P(ba, None, None, None, g_ax, None)
        )
        flags["attn_scores_shard"] = NamedSharding(
            mesh, P(ba, None, g_ax, None, None)
        )
    if "moe_y_shard" in perf:
        flags.pop("moe_y_shard")
        flags["moe_y_shard"] = NamedSharding(
            mesh,
            P(
                shd._maybe("model", cfg.n_experts, mesh) and None,
                "data",
                shd._maybe("model", cfg.d_model, mesh),
            ),
        )
    if "moe_gathered_shard" in perf:
        flags.pop("moe_gathered_shard")
        flags["moe_gathered_shard"] = NamedSharding(mesh, P(None, "data", None))
    if "moe_decode_local" in perf:
        flags["moe_decode_local"] = NamedSharding(
            mesh, P(shd._maybe("model", cfg.n_experts, mesh), None, None)
        )
    _layers.set_perf_flags(**flags)

    params_shape = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    pspecs = shd.param_specs(cfg, params_shape, mesh)
    p_shardings = shd.to_shardings(pspecs, mesh)

    if kind == "train":
        total_params, _ = cfg.param_counts()
        tcfg = ts.TrainConfig(
            opt=optimizer.OptConfig(kind=cfg.optimizer),
            microbatches=(
                mb_override
                if mb_override is not None
                else microbatches_for(cfg, shape)
            ),
            accum_dtype="bfloat16" if total_params > 1e11 else "float32",
        )
        step_fn = ts.make_train_step(cfg, tcfg, grad_shardings=p_shardings)
        opt_shape = jax.eval_shape(
            lambda: optimizer.init(tcfg.opt, params_shape)
        )
        ospecs = shd.opt_specs(cfg, opt_shape, pspecs, mesh, zero=True)
        state_shape = {"params": params_shape, "opt": opt_shape}
        state_shardings = {
            "params": p_shardings,
            "opt": shd.to_shardings(ospecs, mesh),
        }
        bspec = shd.batch_spec(cfg, shape, mesh)
        b_shardings = {
            k: (NamedSharding(mesh, sp) if sp is not None else None)
            for k, sp in bspec.items()
        }
        batch_shape = input_specs(cfg, shape)
        b_shardings = {k: b_shardings.get(k) for k in batch_shape}
        fn = jax.jit(
            step_fn,
            in_shardings=(state_shardings, b_shardings),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,),
        )
        return fn.lower(state_shape, batch_shape), mesh

    if kind == "prefill":
        mode = "prefill" if cfg.causal else "train"

        def prefill(params, batch):
            logits, aux, cache = lm.forward(
                cfg,
                params,
                tokens=batch.get("tokens"),
                embeds=batch.get("embeds"),
                mode=mode,
            )
            # serving returns last-position logits + the cache
            return logits[:, -1], cache

        batch_shape = {
            k: v for k, v in input_specs(cfg, shape).items() if k != "labels"
        }
        bspec = shd.batch_spec(cfg, shape, mesh)
        b_shardings = {k: (NamedSharding(mesh, bspec[k]) if bspec.get(k) else None) for k in batch_shape}
        fn = jax.jit(prefill, in_shardings=(p_shardings, b_shardings))
        return fn.lower(params_shape, batch_shape), mesh

    # decode
    cache_shape = lm.cache_spec(cfg, shape.global_batch, shape.seq_len)
    cspecs = shd.cache_specs(cfg, shape, mesh, cache_shape)
    c_shardings = shd.to_shardings(cspecs, mesh)
    inp = input_specs(cfg, shape)
    ba = shd.batch_axes(mesh)
    dp = int(np.prod([mesh.shape[a] for a in ba]))
    tok_sharded = shape.global_batch % dp == 0 and shape.global_batch >= dp
    tok_spec = P(ba) if tok_sharded else P()
    if cfg.frontend != "none":
        tok_spec = P(ba, None) if tok_sharded else P(None, None)

    def decode(params, cache, token, pos):
        return lm.decode_step(cfg, params, cache, token, pos)

    fn = jax.jit(
        decode,
        in_shardings=(
            p_shardings,
            c_shardings,
            NamedSharding(mesh, tok_spec),
            NamedSharding(mesh, P()),
        ),
        donate_argnums=(1,),
    )
    return fn.lower(params_shape, cache_shape, inp["token"], inp["pos"]), mesh


def _compile_stats(lowered) -> dict:
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    return {
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(
                getattr(mem, "peak_memory_in_bytes", 0)
                or (
                    getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)
                )
            ),
        },
        "cost": {
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
        },
        "collectives": coll,
        "collective_bytes_per_device": int(sum(v["bytes"] for v in coll.values())),
    }


def _extrapolate(arch: str, shape_name: str, multi_pod: bool, cfg, perf: tuple = ()) -> dict:
    """XLA's cost analysis counts each `while` body once, so scan-over-layers
    (and scan-over-microbatches) programs under-report.  We compile two
    reduced-depth clones (1 and 2 superblock groups, microbatches=1) and
    extrapolate linearly:  f(G) = f1 + (G-1) * (f2 - f1).  The per-group
    slope captures per-layer fwd+bwd+optimizer; the intercept captures
    embed/LM-head/loss.  Microbatching does not change total step FLOPs
    (same tokens), so mb=1 clones are exact for cost accounting."""
    import dataclasses

    period = cfg.superblock
    groups = cfg.n_layers // period
    out = {"groups": groups}
    stats = {}
    lm.set_unroll_scan(True)
    try:
        for g in (1, 2):
            clone = dataclasses.replace(cfg, n_layers=period * g)
            lowered, _ = lower_cell(
                arch, shape_name, multi_pod, cfg_override=clone, mb_override=1, perf=perf
            )
            stats[g] = _compile_stats(lowered)
    finally:
        lm.set_unroll_scan(False)
    f1, f2 = stats[1]["cost"]["flops"], stats[2]["cost"]["flops"]
    b1, b2 = (
        stats[1]["cost"]["bytes_accessed"],
        stats[2]["cost"]["bytes_accessed"],
    )
    c1, c2 = (
        stats[1]["collective_bytes_per_device"],
        stats[2]["collective_bytes_per_device"],
    )
    out["flops_per_device"] = f1 + (groups - 1) * (f2 - f1)
    out["bytes_per_device"] = b1 + (groups - 1) * (b2 - b1)
    out["collective_bytes_per_device"] = c1 + (groups - 1) * (c2 - c1)
    out["g1"] = {
        "flops": f1,
        "bytes": b1,
        "coll": c1,
        "collectives": stats[1]["collectives"],
    }
    out["g2"] = {"flops": f2, "bytes": b2, "coll": c2}
    return out


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    out_dir: Path,
    extrapolate: bool = True,
    perf: tuple = (),
    mb_override=None,
) -> dict:
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch}__{shape_name}__{mesh_name}"
    ok, reason = cell_supported(cfg, shape)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "supported": ok,
    }
    if not ok:
        rec["skip_reason"] = reason
        _write(out_dir, cell, rec)
        return rec
    rec["perf_flags"] = list(perf)
    t0 = time.time()
    try:
        lowered, mesh = lower_cell(
            arch, shape_name, multi_pod, perf=perf, mb_override=mb_override
        )
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        rec.update(_compile_stats(lowered))
        rec["compile_s"] = round(time.time() - t1, 1)
        if extrapolate:
            rec["extrapolated"] = _extrapolate(arch, shape_name, multi_pod, cfg, perf=perf)
        total, active = cfg.param_counts()
        rec["params_total"] = int(total)
        rec["params_active"] = int(active)
        rec["tokens"] = shape.tokens
        rec["status"] = "ok"
        ex = rec.get("extrapolated", {})
        print(
            f"[dryrun] {cell}: OK lower={rec['lower_s']}s compile={rec['compile_s']}s "
            f"flops/dev={ex.get('flops_per_device', rec['cost']['flops']):.3e} "
            f"mem(temp)={rec['memory']['temp_bytes']/2**30:.2f}GiB "
            f"coll/dev={ex.get('collective_bytes_per_device', rec['collective_bytes_per_device'])/2**20:.1f}MiB"
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {cell}: FAIL {rec['error']}")
    _write(out_dir, cell, rec)
    return rec


def _write(out_dir: Path, cell: str, rec: dict):
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell}.json").write_text(json.dumps(rec, indent=1, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(RESULTS))
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument(
        "--perf",
        default="",
        help="comma-separated §Perf flags: paired_causal, moe_rs, "
        "moe_bf16_combine, cp_decode",
    )
    ap.add_argument("--mb", type=int, default=None, help="override microbatches")
    args = ap.parse_args()
    out_dir = Path(args.out)
    perf = tuple(f for f in args.perf.split(",") if f)

    cells = []
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))
    done = 0
    for a, s, mp in cells:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        f = out_dir / f"{a}__{s}__{mesh_name}.json"
        if args.skip_done and f.exists():
            try:
                if json.loads(f.read_text()).get("status") == "ok":
                    continue
            except Exception:
                pass
        run_cell(a, s, mp, out_dir, perf=perf, mb_override=args.mb)
        done += 1
    print(f"[dryrun] swept {done} cells -> {out_dir}")


if __name__ == "__main__":
    main()
