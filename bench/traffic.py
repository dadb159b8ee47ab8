"""One general generator for every traffic mix, driven by its data file.

A mix (``bench/traffic/<name>.json``) holds only parameters:

``arrival``          "closed": one client sends its next request when the
                     previous answer returns; "poisson": an open loop with
                     exponential gaps at ``rate_rps`` requests per second.
``set_size``         queries per query set (a set is split into requests
                     in order; the last request of a set may be short).
``request_rows``     {"kind": "fixed", "rows": r} or
                     {"kind": "geometric", "p": p, "max": m}.
``query_sets``       distinct query sets made in set-up; the window cycles
                     through them. The index keeps no result cache, so a
                     repeated set costs what a fresh one does.
``query_jitter``     noise added to the database row each query starts from.
``batch_window_s``   the serve queue's batching window.
``check_sample``     answered queries compared with the reference per run.

Queries come from the run's seed; sizes and arrival gaps of an open loop
come from a fixed stream that the seed only permutes, so every seed offers
the same work in another order.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import jax
import numpy as np

from bench import data as bench_data

SIZE_STREAM_SEED = 0x5eed


@dataclass(frozen=True)
class Request:
    set_index: int
    row0: int
    rows: int
    due_s: float          # offset from the window start; 0 for a closed loop


def query_sets(mix: dict, corpus: jax.Array, seed: int) -> List[np.ndarray]:
    """``mix["query_sets"]`` host arrays of (set_size, dim) float32."""
    base = jax.random.PRNGKey(seed)
    return [np.asarray(bench_data.queries_like(
        jax.random.fold_in(base, i), corpus, mix["set_size"],
        mix["query_jitter"])) for i in range(mix["query_sets"])]


def _row_counts(spec: dict, total: int, rng: np.random.Generator):
    if spec["kind"] == "fixed":
        return [spec["rows"]] * (-(-total // spec["rows"]))
    if spec["kind"] == "geometric":
        n = rng.geometric(spec["p"], size=total)
        return list(np.minimum(n, spec["max"]))
    raise ValueError(f"unknown request_rows kind {spec['kind']!r}")


class Schedule:
    """Request ``i`` of a run: which set, which rows, when it is due.

    Fixed sizes split each set in order. Geometric sizes and Poisson gaps
    are drawn from one fixed stream and shuffled by ``seed``. A closed loop
    has no due times; an open loop has them for ``seconds`` and beyond.
    """

    def __init__(self, mix: dict, seed: int, seconds: float):
        fixed = np.random.default_rng(SIZE_STREAM_SEED)
        order = np.random.default_rng(seed)
        set_size = mix["set_size"]
        counts, row = [], 0
        for n in _row_counts(mix["request_rows"], set_size, fixed):
            if row >= set_size:
                break
            counts.append(int(min(n, set_size - row)))
            row += counts[-1]
        if mix["request_rows"]["kind"] != "fixed":
            counts = [int(n) for n in order.permutation(counts)]
        starts = np.cumsum([0] + counts[:-1])
        self.per_set = [(int(s), n) for s, n in zip(starts, counts)]
        self.n_sets = mix["query_sets"]
        self.closed = mix["arrival"] == "closed"
        if self.closed:
            self.due = None
        elif mix["arrival"] == "poisson":
            horizon = int(2 * mix["rate_rps"] * seconds) + 64
            gaps = fixed.exponential(1.0 / mix["rate_rps"], size=horizon)
            self.due = np.cumsum(order.permutation(gaps))
        else:
            raise ValueError(f"unknown arrival {mix['arrival']!r}")

    def __getitem__(self, i: int) -> Request:
        row0, rows = self.per_set[i % len(self.per_set)]
        set_index = (i // len(self.per_set)) % self.n_sets
        due = 0.0 if self.closed else float(self.due[i])
        return Request(set_index, row0, rows, due)
