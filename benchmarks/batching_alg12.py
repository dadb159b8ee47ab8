"""Paper Algorithms 1 vs 2: naive per-query entry points vs gather-style
grouped batching (their parallel-friendly contribution), plus the TPU-native
batch-major search that makes the workaround unnecessary. Results must be identical;
the timing gap is the contribution."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import K, dataset, measure_qps, print_table, save
from repro.core import IndexParams, TunedGraphIndex, recall_at_k
from repro.core.batching import search_grouped, search_naive


def run():
    data, queries, ti = dataset(4000)
    dim = data.shape[1]
    idx = TunedGraphIndex(IndexParams(
        pca_dim=dim, antihub_keep=1.0, ep_clusters=16, ef_search=64,
        graph_degree=16, build_knn_k=16, build_candidates=32)).fit(data)
    q = queries[:64]

    t0 = time.perf_counter()
    d1, i1 = search_naive(idx, q, K)
    t_naive = time.perf_counter() - t0
    t0 = time.perf_counter()
    d2, i2 = search_grouped(idx, q, K)
    t_grouped = time.perf_counter() - t0
    qps_batched = measure_qps(lambda qs: idx.search(qs, K)[0], q, repeats=3)

    same = (i1 == i2).mean()
    rows = [
        ["Alg.1 naive loop", f"{len(q) / t_naive:.1f}", ""],
        ["Alg.2 grouped", f"{len(q) / t_grouped:.1f}",
         f"x{t_naive / t_grouped:.2f} vs Alg.1"],
        ["batched (TPU-native)", f"{qps_batched:.1f}",
         f"x{qps_batched * t_naive / len(q):.2f} vs Alg.1"],
        ["results identical", f"{same:.3f}", "(Alg.1 == Alg.2)"],
    ]
    headers = ["method", "QPS", "note"]
    print_table("Algorithm 1 vs 2 vs batched", headers, rows)
    save("batching_alg12", rows, headers)
    return rows


if __name__ == "__main__":
    run()
