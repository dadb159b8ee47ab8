"""Decide ``correct``: the window's answers against the plain reference.

Every answer is checked for form (k ids in [0, N), no id twice in a row,
finite distances in ascending order). A sample of answered queries, drawn
from the run's seed, is searched again by ``reference.search`` over every
raw corpus row, and two numbers are compared with the configuration's
limits:

``recall_at_10``  share of the reference's 10 nearest rows that the answer
                  holds, averaged over the sample (limit from below).
``dist_err``      how far the distances the answer reports lie from the
                  float64 distances of the rows it names, as a share of the
                  query's 10th-nearest distance; the widest over the sample
                  (limit from above). A configuration whose search reports
                  distances in a projected space (``"distances":
                  "offset"``) is held to the spread of that error within
                  each answer, since a projection that drops only noise
                  adds the same amount to every distance of one query.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from bench import reference


@dataclass
class Answers:
    """Host copies of what the window answered, one row per query."""
    set_index: np.ndarray     # (A,) which query set
    row: np.ndarray           # (A,) row within the set
    dists: np.ndarray         # (A, k)
    ids: np.ndarray           # (A, k)
    failed: int               # queries answered with a failure, or never


def gather(done: List[tuple], k: int) -> Answers:
    """Flatten ``(request, result)`` pairs of the window into rows."""
    sets, rows, dists, ids, failed = [], [], [], [], 0
    for req, result in done:
        if not result:                     # SearchFailure is falsy
            failed += req.rows
            continue
        d, i = result
        sets.append(np.full(req.rows, req.set_index))
        rows.append(np.arange(req.row0, req.row0 + req.rows))
        dists.append(np.asarray(d, np.float32).reshape(req.rows, -1))
        ids.append(np.asarray(i).reshape(req.rows, -1).astype(np.int64))
    if not sets:
        empty = np.zeros((0, k))
        return Answers(np.zeros(0, int), np.zeros(0, int), empty,
                       empty.astype(np.int64), failed)
    return Answers(np.concatenate(sets), np.concatenate(rows),
                   np.concatenate(dists), np.concatenate(ids), failed)


def malformed(ans: Answers, n_rows: int, k: int) -> int:
    """Answers not of the promised form."""
    if ans.ids.shape[1] != k:
        return int(ans.ids.shape[0])
    ids, d = ans.ids, ans.dists
    bad = np.any((ids < 0) | (ids >= n_rows), axis=1)
    srt = np.sort(ids, axis=1)
    bad |= np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    bad |= ~np.all(np.isfinite(d), axis=1)
    bad |= np.any(d[:, 1:] < d[:, :-1], axis=1)
    return int(bad.sum())


def compare(ans: Answers, sets: List[np.ndarray], corpus, k: int,
            limits: Dict, sample: int, seed: int) -> Tuple[dict, dict]:
    """(checks, readings): each check is {"value", "limit"}; the name's
    suffix says which side the limit holds (``_min`` / ``_max``)."""
    n_rows = int(corpus.shape[0])
    rng = np.random.default_rng([seed, 7])
    total = ans.ids.shape[0]
    pick = np.sort(rng.choice(total, size=min(sample, total), replace=False))
    queries = np.stack([sets[s][r] for s, r in
                        zip(ans.set_index[pick], ans.row[pick])]) \
        if pick.size else np.zeros((0, corpus.shape[1]), np.float32)
    got_d, got_i = ans.dists[pick], ans.ids[pick]
    if pick.size:
        ref_d, ref_i = reference.search(queries, corpus, k)
        hits = (got_i[:, :, None] == ref_i[:, None, :]).any(-1) \
            & (got_i >= 0)
        recall = float(np.mean(hits.sum(1) / k))
        exact = reference.exact_sqdist(queries, corpus, got_i)
        scale = np.maximum(ref_d[:, k - 1].astype(np.float64), 1e-30)
        off = exact - got_d
        if limits["distances"] == "offset":
            err = (np.nanmax(off, 1) - np.nanmin(off, 1)) / scale
        else:
            err = np.nanmax(np.abs(off), 1) / scale
        dist_err = float(np.nanmax(err)) if np.isfinite(err).any() \
            else float("inf")
    else:
        recall, dist_err = 0.0, float("inf")
    checks = {
        "recall_at_10_min": {"value": recall,
                             "limit": limits["recall_at_10_min"]},
        "dist_err_max": {"value": dist_err, "limit": limits["dist_err_max"]},
        "malformed_max": {"value": malformed(ans, n_rows, k), "limit": 0},
        "failed_max": {"value": ans.failed, "limit": 0},
    }
    return checks, {"recall_at_10": recall, "sampled": int(pick.size)}


def holds(checks: dict) -> bool:
    ok = True
    for name, c in checks.items():
        if name.endswith("_min"):
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)
