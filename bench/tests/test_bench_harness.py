"""Discovery by name, exact percentiles, the peaks table, and the shape of
``BENCHMARK.json``."""

import json
import re

import pytest

from bench import harness as H

SPEC = H.load_json(H.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 99, 5.0),
    (list(range(1, 101)), 99, 99.0),
    (list(range(1, 101)), 95, 95.0),
    (list(range(100, 0, -1)), 50, 50.0),
    ([3.0, 1.0, 2.0], 95, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 25, 1.0),
    ([1.0, 2.0, 3.0, 4.0], 26, 2.0),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert H.percentile(values, q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        H.percentile([], 99)


def test_peaks_known_and_unknown():
    p = H.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(H.BenchError, match="no peaks"):
        H.peaks("TPU v9 imaginary")


def test_roofline_names_its_bound():
    p = H.peaks("TPU v5 lite")
    t, bound = H.roofline_s(2 * 128 ** 3, 2 * 128 * 128 * 4, p)
    assert bound == "memory" and t == pytest.approx(131072 / 819e9)
    t, bound = H.roofline_s(1e15, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files(w):
    cell = H.find_cell(w["name"], SPEC)
    assert cell.chips == w["chips"] in (1, 4)
    loop = H.load_module("loops", cell.traffic["loop"])
    assert callable(loop.run)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(H.load_module("metrics", m["name"]).read)
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_unknown_cell_and_missing_file_are_errors(tmp_path):
    with pytest.raises(H.BenchError, match="unknown workload"):
        H.find_cell("no.such.cell", SPEC)
    with pytest.raises(H.BenchError, match="no metric file"):
        H.load_module("metrics", "no_such_metric")


def test_a_cell_added_as_files_is_found(tmp_path):
    """A later PR adds a cell, a mix, a loop kind and a metric as new files
    and entries only: the harness finds each by its name."""
    base = tmp_path / "bench"
    for d in ("configs", "traffic", "loops", "metrics"):
        (base / d).mkdir(parents=True)
    (base / "configs" / "cfg_x.json").write_text(json.dumps({"name": "cfg_x"}))
    (base / "traffic" / "mix.y.json").write_text(json.dumps({"loop": "kind_z"}))
    (base / "loops" / "kind_z.py").write_text("def run(run):\n    return 7\n")
    (base / "metrics" / "m.q.py").write_text("def read(r):\n    return 1.5\n")
    spec = {"workloads": [{"name": "cfg_x.mix.y", "config": "cfg_x",
                           "traffic": "mix.y", "chips": 1, "why": "x"}],
            "end_to_end": [{"name": "e", "unit": "s", "workloads": ["cfg_x.mix.y"]},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "m.q", "moves": "e", "unit": "%"},
                          {"name": "other", "moves": "f", "unit": "%"}]}
    cell = H.find_cell("cfg_x.mix.y", spec, base=base)
    assert [m["name"] for m in cell.per_layer] == ["m.q"]
    assert H.load_module("loops", "kind_z", base=base).run(None) == 7
    assert H.load_module("metrics", "m.q", base=base).read(None) == 1.5


def test_benchmark_json_keeps_to_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (H.ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["better"] in ("lower", "higher")
    assert len(json.dumps(SPEC)) < 64 << 10
