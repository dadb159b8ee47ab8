"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 40
    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 40 --control

Makes the cell's set-up once, then measures one window per seed and
compares its answers as a run of ``bench/run.py`` does. Without
``--control`` this reads the program; with it, the control stands in for
the system under test behind the same serve queue: the plain reference
with every distance product taken as three bfloat16 passes
(``reference.py``'s "bf16x3", the arithmetic of ``Precision.HIGH``), the
step below the float32-at-HIGHEST the configurations state. The
benchmark's own runs never run this. Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, run  # noqa: E402

ROW_BLOCK = 16384


class ControlIndex:
    """Brute force at bf16x3 in the program's place (``Index`` surface)."""

    def __init__(self, data):
        self.data = data

    @property
    def dim(self) -> int:
        return int(self.data.shape[1])

    def search(self, queries, k: int, params=None):
        return reference._topk_block(queries, self.data, k, ROW_BLOCK,
                                     "bf16x3")


def readings(workload: str, seeds, seconds: float, control: bool):
    """One result object per seed, with the seed and the side read."""
    t0 = time.perf_counter()
    cell = run.prepare(workload, ControlIndex if control else None)
    out = []
    for i, seed in enumerate(seeds):
        res = run.measure(cell, seed, seconds, False, free=False,
                          t_start=t0 if i == 0 else time.perf_counter())
        res.update(seed=seed, side="control" if control else "program")
        out.append(res)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    for res in readings(args.workload, seeds, args.seconds, args.control):
        print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
