"""BENCHMARK.json keeps to its contract, and every cell, configuration,
driver and per-layer metric it names resolves to a file of its own."""
import re

import pytest

from bench.lib import common

BM = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = {c["name"]: c for c in BM["workloads"]}


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    assert 1 <= BM["run_seconds"] <= 51


def test_entries_have_only_their_keys():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (common.ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert set(m) - {"workloads"} <= {"name", "unit", "better", "bound", "source",
                                          "layer", "moves"}
        assert m["better"] in ("lower", "higher")


def test_names_and_units():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in BM["end_to_end"] + BM["per_layer"])
    assert all(NAME.match(w["traffic"]) for w in BM["workloads"])
    assert all(NAME.match(k) for c in BM["configs"] for k in c["reduced"])


def test_bounds():
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BM["end_to_end"])


def test_at_most_half_the_cells_take_four_chips():
    assert sum(w["chips"] == 4 for w in BM["workloads"]) <= max(1, len(BM["workloads"]) // 2)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_resolves(cell):
    w = CELLS[cell]
    work = common.workload(cell)
    cfg = common.config(w["config"])
    assert work["config"] == w["config"] and work["chips"] == w["chips"]
    assert cfg["name"] == w["config"]
    assert (common.BENCH / "drivers" / f"{work['driver']}.py").is_file()
    assert {"served_gap_sigma"} <= set(work["limits"]) or work["driver"] == "train_step"
    e2e = [m["name"] for m in BM["end_to_end"] if cell in m.get("workloads", [cell])]
    assert "setup_s" in e2e and len(e2e) >= 2
    per = [m for m in BM["per_layer"] if cell in m.get("workloads", [cell])]
    assert per and all(m["moves"] in e2e for m in per)


@pytest.mark.parametrize("metric", [m["name"] for m in BM["per_layer"]])
def test_metric_reader_resolves(metric):
    assert callable(common.metric_reader(metric).read)


def test_configs_name_their_cuts():
    for c in BM["configs"]:
        cfg = common.config(c["name"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["published"]) == set(c["reduced"])
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size")) and "head" not in key


def test_check_fits_the_budget():
    """2 + 14 runs a cell, each run_seconds + 60, 180 s a cell to compile
    and 1200 spare, for the full 24 cells, within 43200 seconds."""
    assert (2 + 14 * 24) * (BM["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
