"""Every entry of BENCHMARK.json resolves its files by name, and the file
keeps the shape the benchmark's readers expect."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH).encode()) <= 64 * 1024


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_config_and_traffic(cell):
    cfg, raw = spec.config(BENCH, cell["config"])
    assert cfg["name"] == cell["config"] and raw
    for key in ("corpus", "index", "k", "search", "max_batch",
                "guarantees", "reduced", "assumed"):
        assert key in cfg, key
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"].startswith(tuple(BENCH["paths"]))
    mix = spec.traffic(cell["traffic"])
    assert mix["arrival"] in ("closed", "poisson")
    assert cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200
    for m in (spec.metrics_for(BENCH, cell["name"], False),
              spec.metrics_for(BENCH, cell["name"], True)):
        assert m, "every cell reports metrics with and without a trace"
    names = [m["name"] for m in spec.metrics_for(BENCH, cell["name"], False)]
    assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("entry", METRICS, ids=lambda m: m["name"])
def test_metric_resolves_its_reducer(entry):
    mod = spec.metric(entry["name"])
    assert callable(mod.reduce)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE) == (
        entry["unit"], entry["better"], entry["source"])
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    if "layer" in entry:
        assert (mod.LAYER, mod.MOVES) == (entry["layer"], entry["moves"])
        assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "bound" not in entry
    else:
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.25


def test_per_layer_cells_report_what_they_move():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells
            moved = spec.metrics_for(BENCH, cell, False)
            assert m["moves"] in {x["name"] for x in moved}


def test_names_are_unique_and_plain():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        spec.workload(BENCH, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.metric("no_such_metric")
