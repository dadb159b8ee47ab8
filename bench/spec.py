"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A configuration is the file its entry names; a traffic mix is
``bench/traffic/<name>.json``; a metric is ``bench/metrics/<name>.py``.
Adding a cell, a mix or a metric adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import List, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> Tuple[dict, bytes]:
    """The configuration's file as parsed, and its bytes (for hashing)."""
    for c in bench["configs"]:
        if c["name"] == name:
            raw = (root / c["file"]).read_bytes()
            return json.loads(raw), raw
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def metric(name: str):
    """The reducer module ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_name = "bench.metrics." + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reducer for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The entries a run reports: end-to-end without a trace, per-layer
    with one; each where its ``workloads`` list names the cell, or has no
    such list."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]
