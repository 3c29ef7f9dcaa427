"""BENCHMARK.json against the benchmark's contract, and every file a cell
names resolving by name."""

import json
import re

import numpy as np
import pytest

from bench_support import BENCH, CELLS, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and (ROOT / p).is_dir()
    assert SPEC["command"][1] == "bench/run.py"
    assert (ROOT / SPEC["command"][1]).is_file()


def test_names_and_units():
    names = []
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[part]:
            assert NAME.match(e["name"]), e["name"]
            names.append((part in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200


def test_end_to_end_bounds():
    names = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    import harness

    c = harness.resolve(cell)
    assert c.config["name"] == cell.split(".")[0]
    assert any(m["name"] == "setup_s" for m in c.end_to_end) and len(c.end_to_end) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert callable(harness.load_module(BENCH / "metrics" / f"{m['name']}.py").read)
    for part in ("setup", "step"):
        for ws in c.traffic.get(part, []):
            for field in ("key", "value", "length"):
                if field in ws and ws[field]["gen"] != "fresh":
                    assert (BENCH / "gen" / f"{ws[field]['gen']}.py").is_file()
    assert (BENCH / "gen" / f"{c.config['key_shape']}.py").is_file()
    assert sum(round(ws["share"] * c.config["wave_size"]) for ws in c.traffic["step"]) \
        == c.config["wave_size"]


def test_per_layer_without_workloads_follows_its_end_to_end_metric(tmp_path):
    """A per-layer entry without ``workloads`` is read in every cell that
    reports the metric it moves, and in no other."""
    import harness

    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append(dict(spec["workloads"][0], name="sparse-50m.other"))
    spec["end_to_end"][1]["workloads"] = ["sparse-50m.ycsb-c"]
    spec["per_layer"].append({"name": "everywhere", "unit": "ms", "better": "lower",
                              "source": "program_span", "layer": "wave pipeline",
                              "moves": spec["end_to_end"][1]["name"]})
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for c in spec["configs"]:
        (tmp_path / c["file"]).write_text((ROOT / c["file"]).read_text())
    assert "everywhere" in [m["name"] for m in harness.resolve("sparse-50m.ycsb-c", tmp_path).per_layer]
    assert "everywhere" not in [m["name"] for m in harness.resolve("sparse-50m.other", tmp_path).per_layer]


@pytest.mark.parametrize("shape", ["sparse"])
def test_key_shapes_deterministic(shape):
    import harness

    a = harness.gen(shape).keys(5000, harness.rng_for(2**31 + 3, 1))
    b = harness.gen(shape).keys(5000, harness.rng_for(2**31 + 3, 1))
    assert np.array_equal(a, b) and a.size == 5000
    assert np.all(a[1:] > a[:-1]) and a[-1] < np.uint64(2**64 - 1)


def test_scrambled_zipfian_hot_rank_share():
    import harness

    z = harness.gen("scrambled_zipfian")
    idx = z.draw(200_000, np.random.default_rng(5), n_items=1_000_000)
    assert idx.min() >= 0 and idx.max() < 1_000_000
    # rank 0 has probability 1/zeta(10^10, 0.99) = 3.78%; it lands where
    # YCSB's FNV hash puts it
    top = int(z.fnvhash64(np.zeros(1, np.uint64))[0] % np.uint64(1_000_000))
    share = np.mean(idx == top)
    assert abs(share - 1 / z.ZETAN) < 0.002
