"""Faithful host-side batching (paper Algorithms 1 & 2).

On TPU, the batch-major beam_search gives every query its own entry point
natively, so the grouping trick is unnecessary there. These reference
implementations reproduce the paper's CPU/Faiss-style execution so the
Algorithm-1-vs-2 comparison (their batching contribution) can be benchmarked:
Algorithm 1 searches one query at a time; Algorithm 2 groups the batch by
optimal entry point and runs one batched search per group — identical results,
more batch parallelism.
"""
from __future__ import annotations

import numpy as np

from repro.core.beam_search import beam_search, dot_gather_dist


def search_naive(index, queries, k: int):
    """Algorithm 1: per-query entry point, single-query searches."""
    q = index.project(queries)
    eps = np.asarray(index.eps.select(q))
    out_d = np.empty((q.shape[0], k), np.float32)
    out_i = np.empty((q.shape[0], k), np.int64)
    for qi in range(q.shape[0]):
        d, i, _ = beam_search(
            q[qi: qi + 1], index.base, index.graph.neighbors,
            eps[qi: qi + 1], ef=max(index.params.ef_search, k), k=k,
            gather_dist=dot_gather_dist)
        out_d[qi] = np.asarray(d[0])
        kept = np.asarray(index.kept_idx)
        ii = np.asarray(i[0])
        out_i[qi] = np.where(ii >= 0, kept[np.maximum(ii, 0)], -1)
    return out_d, out_i


def search_grouped(index, queries, k: int):
    """Algorithm 2: group queries by entry point; batch within groups."""
    q = index.project(queries)
    eps = np.asarray(index.eps.select(q))
    out_d = np.empty((q.shape[0], k), np.float32)
    out_i = np.empty((q.shape[0], k), np.int64)
    kept = np.asarray(index.kept_idx)
    for ep in np.unique(eps):                      # paper's L2
        sel = np.nonzero(eps == ep)[0]             # paper's L3
        batch = q[sel]                             # paper's L4
        d, i, _ = beam_search(                     # paper's L7 (batched)
            batch, index.base, index.graph.neighbors,
            np.full((len(sel),), ep, np.int32),
            ef=max(index.params.ef_search, k), k=k,
            gather_dist=dot_gather_dist)
        out_d[sel] = np.asarray(d)
        ii = np.asarray(i)
        out_i[sel] = np.where(ii >= 0, kept[np.maximum(ii, 0)], -1)
    return out_d, out_i
