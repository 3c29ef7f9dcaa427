"""Benchmark-level reproduction assertions: the paper's claims must hold in
the implemented system + calibrated models (not just be printed)."""

import numpy as np
import pytest

from benchmarks.table1_memory import overhead
from repro.core import perfmodel, rolex_model


def test_table1_qualitative_contract():
    """Ordering + eps sensitivity (paper Table 1): clustered datasets cost
    much more than smooth ones; eps=16 reclaims osmc/face."""
    ov = {ds: overhead(ds, 8) for ds in ("sparse", "wiki", "amzn", "osmc", "face")}
    assert ov["face"] > ov["osmc"] > ov["amzn"] > ov["sparse"]
    assert ov["face"] > 4 * ov["sparse"]  # the pathological cases hurt
    assert overhead("osmc", 16) < 0.6 * ov["osmc"]
    assert overhead("face", 16) < 0.6 * ov["face"]


def test_osmc_at_eps16_matches_paper():
    """Paper: osmc drops from 74% to 35% at eps=16 — our generator lands on
    the same 35% figure."""
    assert abs(overhead("osmc", 16) - 0.35) < 0.08


def test_insert_is_stitch_bound_not_compute_bound():
    """Fig 13: DPA-side bytes/insert measured on the paper's per-leaf stitch
    stream pushes the model into the ~1-2.5 MOPS band, an order below UPDATE
    throughput.  The batched pipeline must then ship measurably FEWER bytes
    per insert (shared parents rebuilt once per cycle, not once per leaf)."""
    from benchmarks.common import build_store

    def bytes_per_insert(batched):
        store = build_store("sparse", n=50_000, cache=False, batched_patch=batched)
        rng = np.random.default_rng(0)
        all_keys, _ = store.items()
        newk = np.setdiff1d(
            rng.integers(0, 2**63, 9000, dtype=np.uint64), all_keys
        )[:4096]
        b0 = store.stats.stitched_dpa_bytes
        store.put(newk, newk)
        return (store.stats.stitched_dpa_bytes - b0) / len(newk), store.depth

    bpi, depth = bytes_per_insert(batched=False)  # the paper's stream
    ins = perfmodel.insert_mops(bpi, depth=depth)
    upd = perfmodel.update_mops(depth=depth)
    assert ins < upd / 3, (ins, upd)
    assert 0.2 < ins < 4.0, f"bytes/insert={bpi}"
    bpi_batched, _ = bytes_per_insert(batched=True)
    assert bpi_batched < bpi, (bpi_batched, bpi)


def test_ycsb_relations_match_fig15():
    """DPA-Store vs ROLEX qualitative wins/losses (Fig 15)."""
    dpa_get = perfmodel.get_mops(3)
    dpa_get_osmc = perfmodel.get_mops(3, 16, 16)
    # GET: DPA-Store wins on sparse/amzn, ROLEX wins on osmc
    assert dpa_get > rolex_model.get_mops("sparse")
    assert dpa_get > rolex_model.get_mops("amzn")
    assert dpa_get_osmc < rolex_model.get_mops("osmc")
    # RANGE: DPA-Store wins everywhere
    assert perfmodel.range_mops(3) > rolex_model.range_mops(10)
    # INSERT: ROLEX wins big
    assert rolex_model.insert_mops() > 3 * perfmodel.insert_mops(70.0)
    # YCSB-A on amzn/osmc: DPA-Store exceeds ROLEX (paper Fig 15) — the
    # patcher ceiling scales with the update FRACTION (resource-separated)
    for ds, eps in (("amzn", (4, 8)), ("osmc", (16, 16))):
        blend = perfmodel.mix_mops({"get": 0.5, "update": 0.5}, 3, *eps)
        assert blend > rolex_model.ycsb_mops("A", ds), (ds, blend)


@pytest.mark.slow
def test_fig16_smoke_rows_cover_shards_and_scan_lengths():
    """The sharded-RANGE sweep must emit schema-valid rows for >= 2 shard
    counts x 2 scan lengths per tier, and the range tier's derived model
    must scale with shard count while the hash broadcast stays flat."""
    from benchmarks import common, fig16_range
    from benchmarks.run import validate_fig16_coverage, validate_rows

    saved_rows, saved_smoke = common.ROWS[:], common.SMOKE
    common.ROWS.clear()
    common.set_smoke(True)
    try:
        fig16_range.run()
        rows = common.ROWS[:]
    finally:
        common.ROWS[:] = saved_rows
        common.set_smoke(saved_smoke)
    assert not validate_rows(rows)
    assert not validate_fig16_coverage(rows)
    # continuation accounting is part of the smoke schema now: every fig16
    # row carries rounds_in_mesh/reissues, and the range tier's steady
    # state has ZERO host re-issues (the in-mesh loop acceptance gate)
    from benchmarks.run import range_continuation_metrics

    cont = range_continuation_metrics(rows)
    for row in rows:
        name = row.split(",", 1)[0]
        assert name in cont, f"{name}: missing continuation fields"
        if name.startswith("fig16/range/"):
            assert cont[name]["range_reissues"] == 0, (name, cont[name])
    # the real-mesh subprocess leg rides the same run() (its rows carry
    # measured_mops/devices instead of depth/fanout)
    assert any(r.startswith("fig16/mesh_cpu/") for r in rows), "mesh leg emitted no rows"
    model, depth = {}, {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        fields = dict(kv.split("=") for kv in derived.split(";"))
        model[name] = float(fields["model_mops"])
        if "depth" in fields:
            depth[name] = int(fields["depth"])
    assert model["fig16/range/shards4/limit10"] > 1.5 * model["fig16/range/shards2/limit10"]
    # broadcast tier: the model is one shard's RANGE MOPS regardless of the
    # shard count (only the per-shard depth, which shrinks with more shards,
    # may move it — never the scale-out the range tier gets)
    if depth["fig16/hash/shards4/limit10"] == depth["fig16/hash/shards2/limit10"]:
        assert model["fig16/hash/shards4/limit10"] == model["fig16/hash/shards2/limit10"]
    else:  # shallower shards at 4 -> per-shard model can only speed up
        assert model["fig16/hash/shards4/limit10"] >= model["fig16/hash/shards2/limit10"]


@pytest.mark.slow
def test_fig17_smoke_rows_cover_modes_and_report_hits():
    """The scan-anchor sweep must emit schema-valid rows for both cache
    modes x >= 2 skews x 2 scan lengths, report a positive measured hit
    rate under Zipf >= 0.9, and the derived model must show the cache
    improving short-scan throughput at that skew."""
    from benchmarks import common, fig17_scan_cache
    from benchmarks.run import (
        anchor_cache_hit_rates,
        validate_fig17_coverage,
        validate_rows,
    )

    saved_rows, saved_smoke = common.ROWS[:], common.SMOKE
    common.ROWS.clear()
    common.set_smoke(True)
    try:
        fig17_scan_cache.run()
        rows = common.ROWS[:]
    finally:
        common.ROWS[:] = saved_rows
        common.set_smoke(saved_smoke)
    assert not validate_rows(rows)
    assert not validate_fig17_coverage(rows)
    hits = anchor_cache_hit_rates(rows)
    model = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        fields = dict(kv.split("=") for kv in derived.split(";"))
        model[name] = float(fields["model_mops"])
    for alpha in ("zipf0.9", "zipf0.99"):
        assert hits[f"fig17/cache/{alpha}/limit10"] > 0.0, hits
        assert (
            model[f"fig17/cache/{alpha}/limit10"]
            > model[f"fig17/nocache/{alpha}/limit10"]
        ), (alpha, model)


@pytest.mark.slow
def test_fig18_smoke_rows_show_rebalance_retention():
    """The rebalance sweep must emit schema-valid rows for both modes x 2
    storm shapes, and the derived metrics must show the claim the feature
    exists for: under the Zipf-0.99 insert storm the rebalancing tier
    retains MORE of its range MOPS and ends with a SMALLER occupancy
    spread than the static tier — with at least one rebalance actually
    fired."""
    from benchmarks import common, fig18_rebalance
    from benchmarks.run import (
        derived_fields,
        rebalance_metrics,
        validate_fig18_coverage,
        validate_rows,
    )

    saved_rows, saved_smoke = common.ROWS[:], common.SMOKE
    common.ROWS.clear()
    common.set_smoke(True)
    try:
        fig18_rebalance.run()
        rows = common.ROWS[:]
    finally:
        common.ROWS[:] = saved_rows
        common.set_smoke(saved_smoke)
    assert not validate_rows(rows)
    assert not validate_fig18_coverage(rows)
    met = rebalance_metrics(rows)
    fired = {}
    for row in rows:
        name, _, derived = row.split(",", 2)
        fired[name] = int(derived_fields(derived)["rebalances"])
    for storm in ("zipf0.99", "seq"):
        on, off = f"fig18/rebalance/{storm}", f"fig18/static/{storm}"
        assert fired[on] > 0, (storm, rows)
        assert fired[off] == 0
        assert met[on]["retention"] > met[off]["retention"], (storm, met)
        assert met[on]["spread_after"] < met[off]["spread_after"], (storm, met)


@pytest.mark.slow
def test_fig19_smoke_rows_show_replication_costs():
    """The replication sweep must emit schema-valid rows across >= 2
    replication factors plus the failover cell, and the derived metrics
    must show what replication buys and bills: write amplification tracks
    R while every replica is in sync, modeled read capacity grows with R,
    and the primary-kill cell reports zero lost acked writes with a
    parseable recovery time."""
    from benchmarks import common, fig19_replication
    from benchmarks.run import (
        replication_metrics,
        validate_fig19_coverage,
        validate_rows,
    )

    saved_rows, saved_smoke = common.ROWS[:], common.SMOKE
    common.ROWS.clear()
    common.set_smoke(True)
    try:
        fig19_replication.run()
        rows = common.ROWS[:]
    finally:
        common.ROWS[:] = saved_rows
        common.set_smoke(saved_smoke)
    assert not validate_rows(rows)
    assert not validate_fig19_coverage(rows)
    met = replication_metrics(rows)
    for r in (1, 2, 3):
        assert met[f"fig19/r{r}/write"]["write_amp"] == pytest.approx(r), met
    assert (
        met["fig19/r1/read"]["model_mops"]
        < met["fig19/r2/read"]["model_mops"]
        < met["fig19/r3/read"]["model_mops"]
    ), met
    fo = met["fig19/failover/r2"]
    assert fo["lost_acked"] == 0 and fo["recovery_keys"] > 0, fo


def test_fig19_gate_rejects_lost_acked_writes():
    """The schema gate itself: a failover cell reporting a nonzero
    lost-acked count, or an R sweep missing its fields, must be flagged."""
    from benchmarks.run import validate_fig19_coverage

    good = [
        f"fig19/r{r}/write,1.0,model_mops=1.0;write_amp={float(r)};"
        f"acked=8;client=8"
        for r in (1, 2)
    ] + [
        f"fig19/r{r}/read,1.0,model_mops={10.0 * r};replicas={r}"
        for r in (1, 2)
    ] + [
        "fig19/failover/r2,1.0,lost_acked=0;recovery_s=0.1;"
        "recovery_keys=9;rebuilds=1;failovers=1"
    ]
    assert not validate_fig19_coverage(good)
    lost = [r.replace("lost_acked=0", "lost_acked=3") for r in good]
    assert any("lost_acked" in p for p in validate_fig19_coverage(lost))
    nofail = good[:-1]
    assert any("failover" in p for p in validate_fig19_coverage(nofail))
    onefactor = [r for r in good if "/r2/" not in r]
    assert any("factors" in p for p in validate_fig19_coverage(onefactor))


def test_fig16_gate_rejects_missing_or_nonzero_continuation_fields():
    """The schema gate itself: a fig16 row without the continuation fields,
    or a range-tier row reporting host re-issues, must be flagged."""
    from benchmarks.run import validate_fig16_coverage

    good = [
        f"fig16/{t}/shards{s}/limit{l},1.0,"
        f"model_mops=1.0;fanout=1.0;depth=3;rounds_in_mesh=2;reissues=0"
        for t in ("range", "hash")
        for s in (2, 4)
        for l in (10, 100)
    ]
    assert not validate_fig16_coverage(good)
    missing = [r.replace(";rounds_in_mesh=2;reissues=0", "") for r in good]
    assert any("rounds_in_mesh" in p for p in validate_fig16_coverage(missing))
    leaked = [r.replace("reissues=0", "reissues=3") for r in good]
    assert any("re-issues" in p for p in validate_fig16_coverage(leaked))


def test_fig10_gate_rejects_missing_or_overlap_free_pipeline_cells():
    """The wave-pipeline schema gate itself: missing pipelined cells, an
    unreported overlap_frac, zero overlap at qd>=2, nonzero overlap at
    qd=1, or a sub-1.2x qd2/qd1 model ratio must all be flagged."""
    from benchmarks.run import validate_fig10_coverage

    def cell(tier, qd, frac, m):
        return (
            f"fig10/pipe/{tier}/qd{qd},2.0,model_mops={m};"
            f"overlap_frac={frac};measured_kops=400.0;issue_us=500.0;"
            f"drain_us=60.0;mops_vs_roofline=0.9"
        )

    good = [
        cell(t, qd, 0.0 if qd == 1 else 0.4, 1.2 * qd)
        for t in ("single", "range")
        for qd in (1, 2, 4)
    ]
    assert not validate_fig10_coverage(good)
    # pipelined cells missing entirely
    assert any(
        "qd1 + qd2" in p
        for p in validate_fig10_coverage([r for r in good if "/range/" not in r])
    )
    # overlap_frac unreported
    dropped = [r.replace("overlap_frac=0.4;", "") for r in good]
    assert any("overlap_frac" in p for p in validate_fig10_coverage(dropped))
    # pipeline degenerated to serial dispatch at qd=2
    flat = [r.replace("overlap_frac=0.4", "overlap_frac=0.0") for r in good]
    assert any("degenerated" in p for p in validate_fig10_coverage(flat))
    # overlap claimed at qd=1 (serial facade must score exactly 0)
    fake = [
        r.replace("overlap_frac=0.0", "overlap_frac=0.2") if "/qd1," in r else r
        for r in good
    ]
    assert any("qd=1" in p for p in validate_fig10_coverage(fake))
    # pipelining gain regression: qd2 model below 1.2x qd1
    slow_rows = [
        r.replace("model_mops=2.4", "model_mops=1.3") if "/qd2," in r else r
        for r in good
    ]
    assert any("1.2x" in p for p in validate_fig10_coverage(slow_rows))


@pytest.mark.slow
def test_fig10_smoke_rows_report_pipeline_overlap():
    """The measured sweep: fig10 must emit pipelined cells for both tiers
    at qd in {1,2,4} with overlap_frac > 0 once waves double-buffer, the
    closed-loop model showing qd2 >= 1.2x qd1 (the acceptance ratio), and
    a roofline comparison in every cell."""
    from benchmarks import common, fig10_queue_depth
    from benchmarks.run import (
        pipeline_metrics,
        validate_fig10_coverage,
        validate_rows,
    )

    saved_rows, saved_smoke = common.ROWS[:], common.SMOKE
    common.ROWS.clear()
    common.set_smoke(True)
    try:
        fig10_queue_depth.run()
        rows = common.ROWS[:]
    finally:
        common.ROWS[:] = saved_rows
        common.set_smoke(saved_smoke)
    assert not validate_rows(rows)
    assert not validate_fig10_coverage(rows)
    met = pipeline_metrics(rows)
    for tier in ("single", "range"):
        for qd in (1, 2, 4):
            name = f"fig10/pipe/{tier}/qd{qd}"
            assert name in met, (name, sorted(met))
            assert met[name]["mops_vs_roofline"] > 0
            if qd == 1:
                assert met[name]["overlap_frac"] == 0.0, met[name]
            else:
                assert met[name]["overlap_frac"] > 0.0, met[name]
        assert (
            met[f"fig10/pipe/{tier}/qd2"]["model_mops"]
            >= 1.2 * met[f"fig10/pipe/{tier}/qd1"]["model_mops"]
        ), met


@pytest.mark.slow
def test_fig16_mesh_leg_runs_on_forced_devices():
    """The real-mesh fig16 leg: a subprocess with 4 forced host devices
    runs the shard_map RANGE wave end to end and reports measured MOPS
    against the roofline; the emitted row must carry all of it."""
    from benchmarks import common, fig16_range
    from benchmarks.run import derived_fields

    saved_rows, saved_smoke = common.ROWS[:], common.SMOKE
    common.ROWS.clear()
    common.set_smoke(True)
    try:
        fig16_range._run_mesh_leg()
        rows = common.ROWS[:]
    finally:
        common.ROWS[:] = saved_rows
        common.set_smoke(saved_smoke)
    assert rows, "mesh leg emitted no rows"
    for row in rows:
        name, _, derived = row.split(",", 2)
        assert name.startswith("fig16/mesh_cpu/shards4/"), name
        fields = derived_fields(derived)
        assert int(fields["devices"]) >= 4
        assert float(fields["measured_mops"]) > 0
        assert float(fields["mops_vs_roofline"]) > 0
        assert int(fields["rounds_in_mesh"]) >= 1


def test_roofline_reader_runs_if_results_exist():
    from benchmarks import roofline

    rows = roofline.load_all()
    if not rows:
        pytest.skip("no dry-run artifacts yet")
    ok = [r for r in rows if "dominant" in r]
    assert ok, "dry-run artifacts exist but none analysable"
    for r in ok:
        assert r["t_compute_s"] >= 0 and r["t_memory_s"] >= 0
        assert r["dominant"] in ("compute", "memory", "collective")


@pytest.mark.slow
def test_fig20_smoke_rows_show_elastic_costs():
    """The elastic sweep must emit schema-valid grow/shrink/snapshot cells,
    lose zero acked writes across both live reshards, restore the snapshot
    bitwise-equal at a different shard count, and show the retention the
    fleet-width change implies (grow raises aggregate model MOPS, shrink
    lowers it)."""
    from benchmarks import common, fig20_elastic
    from benchmarks.run import (
        elastic_metrics,
        validate_fig20_coverage,
        validate_rows,
    )

    saved_rows, saved_smoke = common.ROWS[:], common.SMOKE
    common.ROWS.clear()
    common.set_smoke(True)
    try:
        fig20_elastic.run()
        rows = common.ROWS[:]
    finally:
        common.ROWS[:] = saved_rows
        common.set_smoke(saved_smoke)
    assert not validate_rows(rows)
    assert not validate_fig20_coverage(rows)
    met = elastic_metrics(rows)
    grow, shrink = met["fig20/grow/2to4"], met["fig20/shrink/4to2"]
    assert grow["lost_acked"] == 0 and shrink["lost_acked"] == 0, met
    assert grow["retention"] > 1.0 > shrink["retention"], met
    snap = met["fig20/snapshot/4to2"]
    assert snap["restore_equal"] == 1 and snap["save_s"] >= 0, met


@pytest.mark.slow
def test_fig21_smoke_rows_show_tenant_isolation():
    """The multi-tenant storm: with admission control ON the victim
    tenant's RANGE throughput must retain >= 0.7 of its solo rate while a
    zipf-0.99 noisy tenant floods the scheduler — and measurably LESS with
    admission OFF; zero cross-tenant rows either way.  The YCSB A-F grid
    must run end to end through the wave scheduler."""
    from benchmarks import common, fig21_tenants
    from benchmarks.run import (
        tenant_metrics,
        validate_fig21_coverage,
        validate_rows,
    )

    saved_rows, saved_smoke = common.ROWS[:], common.SMOKE
    common.ROWS.clear()
    common.set_smoke(True)
    try:
        fig21_tenants.run()
        rows = common.ROWS[:]
    finally:
        common.ROWS[:] = saved_rows
        common.set_smoke(saved_smoke)
    assert not validate_rows(rows)
    assert not validate_fig21_coverage(rows)
    met = tenant_metrics(rows)
    on = met["fig21/storm/admission"]
    off = met["fig21/storm/noadmission"]
    assert on["retention"] >= 0.7, met
    assert off["retention"] < on["retention"], met
    assert on["leaked"] == 0 and off["leaked"] == 0, met
    assert on["noisy_refused_keys"] > 0, met  # admission actually engaged
    for wl in "ABCDEF":
        cell = met[f"fig21/ycsb/{wl}"]
        assert cell["kops"] > 0 and cell["leaked"] == 0, (wl, cell)


def test_fig21_gate_rejects_leaks_and_collapsed_retention():
    """The multi-tenant schema gate itself: a storm cell leaking rows,
    victim retention below 0.7, admission OFF not measurably worse than
    ON, or a missing YCSB cell must all be flagged."""
    from benchmarks.run import validate_fig21_coverage

    good = [
        "fig21/storm/admission,10.0,retention=0.95;leaked=0;"
        "victim_alone_kops=5.0;victim_storm_kops=4.7;"
        "noisy_refused_keys=900;waves=6",
        "fig21/storm/noadmission,90.0,retention=0.12;leaked=0;"
        "victim_alone_kops=5.0;victim_storm_kops=0.6;"
        "noisy_refused_keys=0;waves=20",
    ] + [
        f"fig21/ycsb/{wl},5.0,kops=2.0;waves=3;retries=0;leaked=0"
        for wl in "ABCDEF"
    ]
    assert not validate_fig21_coverage(good)
    leaked = [r.replace("leaked=0", "leaked=4") for r in good]
    assert any("isolation" in p for p in validate_fig21_coverage(leaked))
    collapsed = [
        r.replace("retention=0.95", "retention=0.41") for r in good
    ]
    assert any("0.7" in p for p in validate_fig21_coverage(collapsed))
    useless = [
        r.replace("retention=0.12", "retention=0.96") for r in good
    ]
    assert any(
        "no measurable protection" in p
        for p in validate_fig21_coverage(useless)
    )
    noycsb = [r for r in good if "/ycsb/E" not in r]
    assert any("ycsb/E" in p for p in validate_fig21_coverage(noycsb))
    nostorm = [r for r in good if "/storm/" not in r]
    assert any(
        "storm/admission" in p for p in validate_fig21_coverage(nostorm)
    )


def test_fig20_gate_rejects_lost_acked_and_unequal_restore():
    """The elastic schema gate itself: a reshard cell losing acked writes,
    a snapshot cell that did not restore bitwise-equal, or a missing mode
    must all be flagged."""
    from benchmarks.run import validate_fig20_coverage

    good = [
        f"fig20/{m}/{c},1.0,model_mops=9.0;retention=1.5;reshard_s=0.4;"
        f"lost_acked=0;spread_after=1.1;resharded=100"
        for m, c in (("grow", "2to4"), ("shrink", "4to2"))
    ] + [
        "fig20/snapshot/4to2,1.0,save_s=0.01;restore_s=0.02;"
        "n_keys=100;restore_equal=1"
    ]
    assert not validate_fig20_coverage(good)
    lost = [r.replace("lost_acked=0", "lost_acked=2") for r in good]
    assert any("lost_acked" in p for p in validate_fig20_coverage(lost))
    unequal = [r.replace("restore_equal=1", "restore_equal=0") for r in good]
    assert any("restore_equal" in p for p in validate_fig20_coverage(unequal))
    noshrink = [r for r in good if "/shrink/" not in r]
    assert any("shrink" in p for p in validate_fig20_coverage(noshrink))


@pytest.mark.slow
def test_fig22_smoke_rows_show_versioned_reads_and_ttl():
    """The versioned sweep must emit schema-valid as_of cells for both
    tiers with every point-in-time read matching its frozen oracle, and a
    TTL cell that physically reclaimed the expiring wave with filtered and
    swept reads bitwise-identical."""
    from benchmarks import common, fig22_versioned
    from benchmarks.run import (
        validate_fig22_coverage,
        validate_rows,
        versioned_metrics,
    )

    saved_rows, saved_smoke = common.ROWS[:], common.SMOKE
    common.ROWS.clear()
    common.set_smoke(True)
    try:
        fig22_versioned.run()
        rows = common.ROWS[:]
    finally:
        common.ROWS[:] = saved_rows
        common.set_smoke(saved_smoke)
    assert not validate_rows(rows)
    assert not validate_fig22_coverage(rows)
    met = versioned_metrics(rows)
    for tier in ("single", "range"):
        cell = met[f"fig22/as_of/{tier}"]
        assert cell["as_of_match"] == 1 and cell["pages"] > 0, met
    ttl = met["fig22/ttl/sweep"]
    assert ttl["reclaimed"] > 0, met
    assert ttl["filter_reclaim_equal"] == 1 and ttl["versioned_expiry"] == 1


def test_fig22_gate_rejects_mismatch_and_empty_sweep():
    """The versioned schema gate itself: an as_of cell diverging from its
    frozen oracle, a TTL sweep that reclaimed nothing under the expiring
    workload, filtered-vs-swept divergence, or a missing cell must all be
    flagged."""
    from benchmarks.run import validate_fig22_coverage

    good = [
        f"fig22/as_of/{t},2.0,as_of_match=1;pages=5;live_get_us=1.0;"
        f"tax=1.4;retained=24"
        for t in ("single", "range")
    ] + [
        "fig22/ttl/sweep,3.0,as_of_match=1;reclaimed=256;"
        "filter_reclaim_equal=1;versioned_expiry=1;sweep_s=0.1"
    ]
    assert not validate_fig22_coverage(good)
    mismatch = [r.replace("as_of_match=1", "as_of_match=0") for r in good]
    assert any("as_of_match" in p for p in validate_fig22_coverage(mismatch))
    empty = [r.replace("reclaimed=256", "reclaimed=0") for r in good]
    assert any("reclaimed" in p for p in validate_fig22_coverage(empty))
    diverged = [
        r.replace("filter_reclaim_equal=1", "filter_reclaim_equal=0")
        for r in good
    ]
    assert any(
        "filter_reclaim_equal" in p for p in validate_fig22_coverage(diverged)
    )
    nosingle = [r for r in good if "/as_of/single" not in r]
    assert any("as_of/single" in p for p in validate_fig22_coverage(nosingle))
