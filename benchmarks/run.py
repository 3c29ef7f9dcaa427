"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run`` — prints ``name,us_per_call,
derived`` CSV rows for every experiment, plus the roofline table derived
from the dry-run artifacts (if present).

``--smoke`` runs the same sweep at tiny sizes (see common.set_smoke),
validates every emitted row against the CSV schema, and writes a
``BENCH_smoke.json`` artifact — this is the CI benchmark gate: it proves
the benchmarks still *run* and still emit well-formed rows, not that the
numbers are paper-comparable.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def validate_rows(rows) -> list:
    """Each row must be ``name,us_per_call,derived`` with a float middle
    field.  Returns a list of parse problems (empty = schema OK)."""
    problems = []
    for row in rows:
        parts = row.split(",", 2)
        if len(parts) != 3:
            problems.append(f"not 3 fields: {row!r}")
            continue
        name, us, derived = parts
        if not name or "/" not in name:
            problems.append(f"bad name field: {row!r}")
        try:
            float(us)
        except ValueError:
            problems.append(f"non-float us_per_call: {row!r}")
        if not derived:
            problems.append(f"empty derived field: {row!r}")
    return problems


def validate_fig16_coverage(rows) -> list:
    """The sharded-RANGE sweep must cover >= 2 shard counts x 2 scan lengths
    per partition tier (fig16 rows are ``fig16/<tier>/shards<N>/limit<L>``),
    and every row must carry parseable ``rounds_in_mesh`` and ``reissues``
    derived fields — the two quantities the in-mesh continuation claim
    rests on (steady-state re-issues must be 0: the device loop resumes
    truncated lanes itself, so a host re-issue is a regression)."""
    problems = []
    for tier in ("range", "hash"):
        shard_counts, limits = set(), set()
        for row in rows:
            name, _, derived = row.split(",", 2)
            parts = name.split("/")
            if len(parts) == 4 and parts[0] == "fig16" and parts[1] == tier:
                shard_counts.add(parts[2])
                limits.add(parts[3])
                fields = derived_fields(derived)
                for key in ("rounds_in_mesh", "reissues"):
                    try:
                        int(fields.get(key, ""))
                    except ValueError:
                        problems.append(f"{name}: missing/bad {key} field")
                if tier == "range" and fields.get("reissues", "") not in ("", "0"):
                    problems.append(
                        f"{name}: steady-state host re-issues must be 0, "
                        f"got {fields['reissues']} (in-mesh loop regression)"
                    )
        if len(shard_counts) < 2 or len(limits) < 2:
            problems.append(
                f"fig16/{tier}: need >= 2 shard counts x 2 scan lengths, "
                f"got shards={sorted(shard_counts)} limits={sorted(limits)}"
            )
    return problems


def validate_fig10_coverage(rows) -> list:
    """The wave-pipeline sweep must cover both tiers (single + range-sharded)
    at queue depths 1 and 2 (rows are ``fig10/pipe/<tier>/qd<q>``); every
    cell must carry parseable ``overlap_frac`` and ``mops_vs_roofline``;
    overlap must be 0 at qd=1 (the serial facade) and > 0 at qd >= 2 —
    waves that stop overlapping mean the double-buffer degenerated back to
    serial dispatch; and the closed-loop model must show qd=2 at >= 1.2x
    the qd=1 throughput (the pipelining claim itself)."""
    problems = []
    for tier in ("single", "range"):
        depths = {}
        for row in rows:
            name, _, derived = row.split(",", 2)
            parts = name.split("/")
            if (
                len(parts) != 4
                or parts[0] != "fig10"
                or parts[1] != "pipe"
                or parts[2] != tier
            ):
                continue
            depths[parts[3]] = fields = derived_fields(derived)
            for key in ("overlap_frac", "mops_vs_roofline", "model_mops"):
                try:
                    float(fields.get(key, ""))
                except ValueError:
                    problems.append(f"{name}: missing/bad {key} field")
            try:
                frac = float(fields.get("overlap_frac", ""))
                qd = int(parts[3][2:])
                if qd == 1 and frac != 0.0:
                    problems.append(
                        f"{name}: overlap_frac must be 0 at qd=1, got {frac}"
                    )
                if qd >= 2 and frac <= 0.0:
                    problems.append(
                        f"{name}: overlap_frac must be > 0 at qd>=2, got "
                        f"{frac} (pipeline degenerated to serial dispatch)"
                    )
            except ValueError:
                pass  # already reported above
        if not {"qd1", "qd2"} <= depths.keys():
            problems.append(
                f"fig10/pipe/{tier}: need qd1 + qd2 cells, "
                f"got {sorted(depths)}"
            )
            continue
        try:
            m1 = float(depths["qd1"]["model_mops"])
            m2 = float(depths["qd2"]["model_mops"])
            if m2 < 1.2 * m1:
                problems.append(
                    f"fig10/pipe/{tier}: qd2 model throughput {m2} < "
                    f"1.2x qd1 {m1} (pipelining gain regression)"
                )
        except (KeyError, ValueError):
            pass  # field problems already reported
    return problems


def validate_fig17_coverage(rows) -> list:
    """The scan-anchor-cache sweep must cover both cache modes x >= 2 Zipf
    skews x >= 2 scan lengths (rows are ``fig17/<mode>/zipf<a>/limit<L>``)."""
    problems = []
    for mode in ("cache", "nocache"):
        skews, limits = set(), set()
        for row in rows:
            name = row.split(",", 1)[0]
            parts = name.split("/")
            if len(parts) == 4 and parts[0] == "fig17" and parts[1] == mode:
                skews.add(parts[2])
                limits.add(parts[3])
        if len(skews) < 2 or len(limits) < 2:
            problems.append(
                f"fig17/{mode}: need >= 2 skews x 2 scan lengths, "
                f"got skews={sorted(skews)} limits={sorted(limits)}"
            )
    return problems


def derived_fields(derived: str) -> dict:
    """Parse a row's ``derived`` column (``k=v;k=v;...``) into a dict —
    the one shared reader for every coverage gate / metric extractor."""
    return dict(kv.split("=", 1) for kv in derived.split(";") if "=" in kv)


def validate_fig18_coverage(rows) -> list:
    """The rebalance sweep must cover both modes x >= 2 storm shapes (rows
    are ``fig18/<mode>/<storm>``) and every row must carry parseable
    ``retention`` and ``spread_after`` derived fields — the two quantities
    the online-rebalance claim rests on."""
    problems = []
    for mode in ("rebalance", "static"):
        storms = set()
        for row in rows:
            name, _, derived = row.split(",", 2)
            parts = name.split("/")
            if len(parts) == 3 and parts[0] == "fig18" and parts[1] == mode:
                storms.add(parts[2])
                fields = derived_fields(derived)
                for key in ("retention", "spread_after"):
                    try:
                        float(fields.get(key, ""))
                    except ValueError:
                        problems.append(f"{name}: missing/bad {key} field")
        if len(storms) < 2:
            problems.append(
                f"fig18/{mode}: need >= 2 storm shapes, got {sorted(storms)}"
            )
    return problems


def validate_fig19_coverage(rows) -> list:
    """The replication sweep must cover >= 2 replication factors with
    parseable ``write_amp`` (write rows) and ``model_mops`` (read rows),
    and the failover cell must report ``lost_acked=0`` — acked writes
    surviving a primary crash is THE replication claim, so a nonzero count
    (or a missing field) fails the smoke gate."""
    problems = []
    factors = set()
    for row in rows:
        name, _, derived = row.split(",", 2)
        parts = name.split("/")
        if len(parts) != 3 or parts[0] != "fig19":
            continue
        fields = derived_fields(derived)
        if parts[1].startswith("r") and parts[2] in ("write", "read"):
            factors.add(parts[1])
            key = "write_amp" if parts[2] == "write" else "model_mops"
            try:
                float(fields.get(key, ""))
            except ValueError:
                problems.append(f"{name}: missing/bad {key} field")
        elif parts[1] == "failover":
            if fields.get("lost_acked", "") != "0":
                problems.append(
                    f"{name}: lost_acked must be 0, got "
                    f"{fields.get('lost_acked', '<missing>')} "
                    f"(acked-write durability regression)"
                )
            try:
                float(fields.get("recovery_s", ""))
            except ValueError:
                problems.append(f"{name}: missing/bad recovery_s field")
    if len(factors) < 2:
        problems.append(
            f"fig19: need >= 2 replication factors, got {sorted(factors)}"
        )
    if not any(r.startswith("fig19/failover/") for r in rows):
        problems.append("fig19: missing failover cell")
    return problems


def validate_fig20_coverage(rows) -> list:
    """The elastic sweep must produce a grow AND a shrink reshard cell plus
    a snapshot round-trip cell (rows are ``fig20/<mode>/<NtoM>``).  Reshard
    cells need parseable ``retention``/``reshard_s`` and ``lost_acked=0`` —
    acked writes surviving a live shard-count change is THE elastic claim,
    so a nonzero count fails the smoke gate.  The snapshot cell needs
    parseable ``save_s``/``restore_s`` and ``restore_equal=1`` (the
    shard-count-independent layout must restore bitwise-equal)."""
    problems = []
    modes = set()
    for row in rows:
        name, _, derived = row.split(",", 2)
        parts = name.split("/")
        if len(parts) != 3 or parts[0] != "fig20":
            continue
        fields = derived_fields(derived)
        modes.add(parts[1])
        if parts[1] in ("grow", "shrink"):
            for key in ("retention", "reshard_s"):
                try:
                    float(fields.get(key, ""))
                except ValueError:
                    problems.append(f"{name}: missing/bad {key} field")
            if fields.get("lost_acked", "") != "0":
                problems.append(
                    f"{name}: lost_acked must be 0, got "
                    f"{fields.get('lost_acked', '<missing>')} "
                    f"(acked-write durability regression across reshard)"
                )
        elif parts[1] == "snapshot":
            for key in ("save_s", "restore_s"):
                try:
                    float(fields.get(key, ""))
                except ValueError:
                    problems.append(f"{name}: missing/bad {key} field")
            if fields.get("restore_equal", "") != "1":
                problems.append(
                    f"{name}: restore_equal must be 1, got "
                    f"{fields.get('restore_equal', '<missing>')} "
                    f"(shard-count-independent restore regression)"
                )
    for mode in ("grow", "shrink", "snapshot"):
        if mode not in modes:
            problems.append(f"fig20: missing {mode} cell")
    return problems


def validate_fig22_coverage(rows) -> list:
    """The versioned sweep must produce an ``as_of`` cell per tier (single
    + range) and the TTL sweep cell (rows are ``fig22/as_of/<tier>`` and
    ``fig22/ttl/sweep``).  Every cell's ``as_of_match`` must be 1 — a
    point-in-time read diverging from its frozen oracle is a correctness
    regression, so it fails the smoke gate rather than shipping as a perf
    datum.  The TTL cell additionally needs ``reclaimed`` nonzero under the
    expiring workload (a sweep that reclaims nothing means expiry never
    fired) and ``filter_reclaim_equal=1`` (reads must be bitwise-identical
    before and after physical reclamation)."""
    problems = []
    cells = set()
    for row in rows:
        name, _, derived = row.split(",", 2)
        parts = name.split("/")
        if len(parts) != 3 or parts[0] != "fig22":
            continue
        fields = derived_fields(derived)
        cells.add(f"{parts[1]}/{parts[2]}")
        if fields.get("as_of_match", "") != "1":
            problems.append(
                f"{name}: as_of_match must be 1, got "
                f"{fields.get('as_of_match', '<missing>')} "
                f"(point-in-time read diverged from its frozen oracle)"
            )
        if parts[1] == "ttl":
            try:
                reclaimed = int(fields.get("reclaimed", ""))
            except ValueError:
                reclaimed = -1
            if reclaimed <= 0:
                problems.append(
                    f"{name}: reclaimed must be > 0 under the expiring "
                    f"workload, got {fields.get('reclaimed', '<missing>')}"
                )
            if fields.get("filter_reclaim_equal", "") != "1":
                problems.append(
                    f"{name}: filter_reclaim_equal must be 1 (filtered and "
                    f"physically-reclaimed reads diverged)"
                )
    for cell in ("as_of/single", "as_of/range", "ttl/sweep"):
        if cell not in cells:
            problems.append(f"fig22: missing {cell} cell")
    return problems


def validate_fig21_coverage(rows) -> list:
    """The multi-tenant sweep must produce BOTH storm cells (admission on
    and off) plus every YCSB A-F cell driven through the wave scheduler
    (rows are ``fig21/storm/<mode>`` and ``fig21/ycsb/<WL>``).  Storm
    cells need parseable ``retention`` and ``leaked=0`` (bitwise
    cross-tenant rows — any leak is an isolation hole); with admission ON
    the victim's RANGE retention must stay >= 0.7 AND beat the
    admission-OFF cell — one noisy tenant not collapsing another's RANGE
    throughput is THE multi-tenant claim, so either failure fails the
    smoke gate."""
    problems = []
    retention = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        parts = name.split("/")
        if len(parts) != 3 or parts[0] != "fig21":
            continue
        fields = derived_fields(derived)
        if parts[1] == "storm":
            try:
                retention[parts[2]] = float(fields.get("retention", ""))
            except ValueError:
                problems.append(f"{name}: missing/bad retention field")
            if fields.get("leaked", "") != "0":
                problems.append(
                    f"{name}: leaked must be 0, got "
                    f"{fields.get('leaked', '<missing>')} "
                    f"(cross-tenant isolation hole)"
                )
        elif parts[1] == "ycsb":
            for key in ("kops", "retries"):
                if key not in fields:
                    problems.append(f"{name}: missing {key} field")
            if fields.get("leaked", "") != "0":
                problems.append(
                    f"{name}: leaked must be 0, got "
                    f"{fields.get('leaked', '<missing>')}"
                )
    for mode in ("admission", "noadmission"):
        if mode not in retention:
            problems.append(f"fig21: missing storm/{mode} cell")
    if {"admission", "noadmission"} <= retention.keys():
        if retention["admission"] < 0.7:
            problems.append(
                f"fig21/storm/admission: victim RANGE retention "
                f"{retention['admission']:.3f} < 0.7 (noisy neighbour "
                f"collapsed the victim despite admission control)"
            )
        if retention["noadmission"] >= retention["admission"]:
            problems.append(
                f"fig21/storm: admission OFF retention "
                f"{retention['noadmission']:.3f} not worse than ON "
                f"{retention['admission']:.3f} — admission control shows "
                f"no measurable protection"
            )
    missing = {f"fig21/ycsb/{wl}" for wl in "ABCDEF"} - {
        r.split(",", 1)[0] for r in rows
    }
    for name in sorted(missing):
        problems.append(f"fig21: missing {name} cell")
    return problems


def tenant_metrics(rows) -> dict:
    """Victim RANGE retention / leak counters per storm cell + scheduler
    throughput per YCSB mix — surfaced in the smoke artifact so the perf
    trajectory records what multi-tenant isolation costs."""
    out = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        if not name.startswith("fig21/"):
            continue
        fields = derived_fields(derived)
        try:
            if "/storm/" in name:
                out[name] = {
                    "retention": float(fields["retention"]),
                    "leaked": int(fields["leaked"]),
                    "victim_storm_kops": float(fields["victim_storm_kops"]),
                    "noisy_refused_keys": int(fields["noisy_refused_keys"]),
                }
            else:
                out[name] = {
                    "kops": float(fields["kops"]),
                    "retries": int(fields["retries"]),
                    "leaked": int(fields["leaked"]),
                }
        except (KeyError, ValueError):
            pass
    return out


def elastic_metrics(rows) -> dict:
    """Reshard retention / wall-clock / lost-acked + snapshot round-trip
    timings per fig20 cell — surfaced in the smoke artifact so the perf
    trajectory records what a live shard-count change costs."""
    out = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        if not name.startswith("fig20/"):
            continue
        fields = derived_fields(derived)
        try:
            if "/snapshot/" in name:
                out[name] = {
                    "save_s": float(fields["save_s"]),
                    "restore_s": float(fields["restore_s"]),
                    "restore_equal": int(fields["restore_equal"]),
                }
            else:
                out[name] = {
                    "retention": float(fields["retention"]),
                    "reshard_s": float(fields["reshard_s"]),
                    "lost_acked": int(fields["lost_acked"]),
                    "spread_after": float(fields["spread_after"]),
                }
        except (KeyError, ValueError):
            pass
    return out


def versioned_metrics(rows) -> dict:
    """Point-in-time read tax + TTL sweep yield per fig22 cell — surfaced
    in the smoke artifact so the trajectory records what the multi-version
    window costs and that expiry keeps physically reclaiming."""
    out = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        if not name.startswith("fig22/"):
            continue
        fields = derived_fields(derived)
        try:
            if "/ttl/" in name:
                out[name] = {
                    "reclaimed": int(fields["reclaimed"]),
                    "filter_reclaim_equal": int(fields["filter_reclaim_equal"]),
                    "versioned_expiry": int(fields["versioned_expiry"]),
                    "sweep_s": float(fields["sweep_s"]),
                }
            else:
                out[name] = {
                    "as_of_match": int(fields["as_of_match"]),
                    "pages": int(fields["pages"]),
                    "tax": float(fields["tax"]),
                    "retained": int(fields["retained"]),
                }
        except (KeyError, ValueError):
            pass
    return out


def replication_metrics(rows) -> dict:
    """Write amplification per replication factor + failover recovery
    numbers — surfaced in the smoke artifact so the perf trajectory
    records the durability bill and the recovery wall-clock."""
    out = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        if not name.startswith("fig19/"):
            continue
        fields = derived_fields(derived)
        try:
            if name.endswith("/write"):
                out[name] = {"write_amp": float(fields["write_amp"])}
            elif name.endswith("/read"):
                out[name] = {"model_mops": float(fields["model_mops"])}
            elif "/failover/" in name:
                out[name] = {
                    "lost_acked": int(fields["lost_acked"]),
                    "recovery_s": float(fields["recovery_s"]),
                    "recovery_keys": int(fields["recovery_keys"]),
                }
        except (KeyError, ValueError):
            pass
    return out


def rebalance_metrics(rows) -> dict:
    """Measured occupancy spread + range-MOPS retention per fig18 cell —
    surfaced in the smoke artifact so the perf trajectory captures how much
    of the scatter-gather advantage survives a skew storm."""
    out = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        if not name.startswith("fig18/"):
            continue
        fields = derived_fields(derived)
        try:
            out[name] = {
                "retention": float(fields["retention"]),
                "spread_after": float(fields["spread_after"]),
            }
        except (KeyError, ValueError):
            pass
    return out


def range_continuation_metrics(rows) -> dict:
    """``range_rounds_in_mesh`` / ``range_reissues`` per fig16/fig17 cell —
    surfaced in the smoke artifact so the perf trajectory records how many
    continuation round-trips the in-mesh loop keeps off the host (and that
    the host re-issue count stays at its steady-state 0)."""
    out = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        if not (name.startswith("fig16/") or name.startswith("fig17/")):
            continue
        fields = derived_fields(derived)
        try:
            out[name] = {
                "range_rounds_in_mesh": int(fields["rounds_in_mesh"]),
                "range_reissues": int(fields["reissues"]),
            }
        except (KeyError, ValueError):
            pass
    return out


def anchor_cache_hit_rates(rows) -> dict:
    """Measured scan-anchor hit rate per fig17 cache cell (parsed from the
    ``hit=`` field of the derived column) — surfaced in the smoke artifact
    so the perf trajectory starts capturing cache behaviour."""
    out = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        if not name.startswith("fig17/cache/"):
            continue
        for field in derived.split(";"):
            if field.startswith("hit="):
                try:
                    out[name] = float(field[4:])
                except ValueError:
                    pass
    return out


def pipeline_metrics(rows) -> dict:
    """Measured wave-pipeline cells per ``fig10/pipe`` tier x depth —
    surfaced in the smoke artifact so the perf trajectory records how much
    dispatch/drain overlap the double-buffer actually wins and how close
    the measured throughput sits to the perfmodel roofline."""
    out = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        if not name.startswith("fig10/pipe/"):
            continue
        fields = derived_fields(derived)
        try:
            out[name] = {
                "overlap_frac": float(fields["overlap_frac"]),
                "mops_vs_roofline": float(fields["mops_vs_roofline"]),
                "measured_kops": float(fields["measured_kops"]),
                "model_mops": float(fields["model_mops"]),
            }
        except (KeyError, ValueError):
            pass
    return out


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(prog="benchmarks.run")
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="tiny sizes; validate CSV schema; write BENCH_smoke.json",
    )
    parser.add_argument(
        "--out",
        default="BENCH_smoke.json",
        help="artifact path for --smoke (default: BENCH_smoke.json)",
    )
    args = parser.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import common

    if args.smoke:
        common.set_smoke(True)
        # fail fast on an unwritable artifact path — not after the sweep
        try:
            with open(args.out, "a"):
                pass
        except OSError as e:
            parser.error(f"cannot write --out {args.out}: {e}")

    from . import (
        bulkload,
        fig9_threads,
        fig10_queue_depth,
        fig11_get,
        fig12_btree,
        fig13_insert_update,
        fig14_models,
        fig15_ycsb,
        fig16_range,
        fig17_scan_cache,
        fig18_rebalance,
        fig19_replication,
        fig20_elastic,
        fig21_tenants,
        fig22_versioned,
        perfmodel_check,
        roofline,
        table1_memory,
    )

    print("name,us_per_call,derived")
    modules = [
        ("perfmodel_check", perfmodel_check),
        ("table1_memory", table1_memory),
        ("fig9_threads", fig9_threads),
        ("fig10_queue_depth", fig10_queue_depth),
        ("fig11_get", fig11_get),
        ("fig12_btree", fig12_btree),
        ("fig13_insert_update", fig13_insert_update),
        ("fig14_models", fig14_models),
        ("fig15_ycsb", fig15_ycsb),
        ("fig16_range", fig16_range),
        ("fig17_scan_cache", fig17_scan_cache),
        ("fig18_rebalance", fig18_rebalance),
        ("fig19_replication", fig19_replication),
        ("fig20_elastic", fig20_elastic),
        ("fig21_tenants", fig21_tenants),
        ("fig22_versioned", fig22_versioned),
        ("bulkload", bulkload),
        ("roofline", roofline),
    ]
    failures = []
    timings = {}
    for name, mod in modules:
        t0 = time.time()
        try:
            mod.run()
            timings[name] = round(time.time() - t0, 2)
            print(f"# {name}: done in {timings[name]:.1f}s", file=sys.stderr)
        except Exception:  # noqa: BLE001 — keep the harness sweeping
            failures.append(name)
            print(f"# {name}: FAILED", file=sys.stderr)
            traceback.print_exc()

    if args.smoke:
        problems = validate_rows(common.ROWS)
        if "fig10_queue_depth" not in failures:
            problems += validate_fig10_coverage(common.ROWS)
        if "fig16_range" not in failures:
            problems += validate_fig16_coverage(common.ROWS)
        if "fig17_scan_cache" not in failures:
            problems += validate_fig17_coverage(common.ROWS)
        if "fig18_rebalance" not in failures:
            problems += validate_fig18_coverage(common.ROWS)
        if "fig19_replication" not in failures:
            problems += validate_fig19_coverage(common.ROWS)
        if "fig20_elastic" not in failures:
            problems += validate_fig20_coverage(common.ROWS)
        if "fig21_tenants" not in failures:
            problems += validate_fig21_coverage(common.ROWS)
        if "fig22_versioned" not in failures:
            problems += validate_fig22_coverage(common.ROWS)
        artifact = {
            "mode": "smoke",
            "rows": common.ROWS,
            "n_rows": len(common.ROWS),
            "schema_ok": not problems,
            "schema_problems": problems,
            "module_seconds": timings,
            "failed_modules": failures,
            "anchor_cache_hit_rates": anchor_cache_hit_rates(common.ROWS),
            "pipeline_metrics": pipeline_metrics(common.ROWS),
            "rebalance_metrics": rebalance_metrics(common.ROWS),
            "replication_metrics": replication_metrics(common.ROWS),
            "elastic_metrics": elastic_metrics(common.ROWS),
            "versioned_metrics": versioned_metrics(common.ROWS),
            "tenant_metrics": tenant_metrics(common.ROWS),
            "range_continuation": range_continuation_metrics(common.ROWS),
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"# smoke artifact: {args.out} "
              f"(rows={len(common.ROWS)}, schema_ok={not problems})",
              file=sys.stderr)
        if problems:
            for p in problems:
                print(f"# schema problem: {p}", file=sys.stderr)
            sys.exit(1)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
