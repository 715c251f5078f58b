"""BENCHMARK.json against the benchmark contract, and every cell,
configuration, traffic mix and metric found by name."""
import json
import math
import re

import pytest
from harness import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
CELLS = [w["name"] for w in BENCH["workloads"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(BENCH) == KEYS
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p
        assert (spec.ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in BENCH["paths"])
    assert len(json.dumps(BENCH).encode()) <= 64 * 1024


def test_run_seconds_fit_the_check_budget():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_lines():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert _line(w["why"])
    for c in BENCH["configs"]:
        assert _line(c["why"]) and _line(c["source"])
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not (k.endswith("_dim") or k.endswith("_rank"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_end_to_end_bounds_and_sources():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    w = spec.cell(BENCH, cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4)
    conf = spec.config(BENCH, w["config"])
    assert {"source", "model", "engine", "reduced"} <= set(conf)
    traffic = spec.traffic(w["traffic"])
    assert callable(spec.driver(traffic["kind"]).setup)
    e2e = [m["name"] for m in spec.end_to_end(BENCH, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer(BENCH, cell)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_loads_and_its_cells_report_what_it_moves(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert _line(m["layer"])
    assert callable(spec.reader(metric).read)
    moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS)


def test_one_layer_name_per_layer_and_roofline_units():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_file_widths_match_the_program(name):
    from harness import runner
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert entry["file"].startswith("bench/")
    conf = spec.config(BENCH, name)
    cfg = runner.model_config(conf)
    assert cfg.d_model == conf["model"]["d_model"]
    assert set(entry["reduced"]) == set(conf["reduced"])
    for k in entry["reduced"]:
        assert k in conf


def test_four_chip_cells_are_at_most_half():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, math.floor(len(CELLS) / 2))
