"""Paper Fig. 3 ablations: each knob alone vs vanilla NSG.

 (a) PCA dim D sweep          — paper best: D=600/768, x1.53 QPS @ recall>=0.9
 (b) AntiHub keep alpha sweep — paper best: alpha=0.9, x1.61 QPS
 (c) entry-point k sweep      — paper best: x1.30 QPS in high-recall regime

We reproduce the *shape* of each trade-off (QPS up, recall held >= 0.9) and
report the speedup of the best config per knob; hop counts are reported for
(c) since entry-point tuning shortens search paths directly.
"""
from __future__ import annotations

from dataclasses import replace

import jax
import numpy as np

from benchmarks.common import K, dataset, measure_qps, print_table, save
from repro.core import IndexParams, TunedGraphIndex, recall_at_k
from repro.core.beam_search import beam_search, dot_gather_dist

BASE = IndexParams(pca_dim=10**9, antihub_keep=1.0, ep_clusters=1,
                   ef_search=64, graph_degree=24, build_knn_k=24,
                   build_candidates=48)


def _measure(idx, queries, ti):
    d, i = idx.search(queries, K)
    r = recall_at_k(i, ti)
    qps = measure_qps(lambda q: idx.search(q, K)[0], queries)
    return r, qps


def run():
    data, queries, ti = dataset()
    dim = data.shape[1]
    base = replace(BASE, pca_dim=dim)
    vanilla = TunedGraphIndex(base).fit(data)
    r0, qps0 = _measure(vanilla, queries, ti)
    print(f"vanilla NSG: recall={r0:.4f} qps={qps0:.1f}")

    rows_a = [["vanilla", dim, round(r0, 4), f"{qps0:.1f}", "x1.00"]]
    for d_r in (dim // 4, dim // 2, 3 * dim // 4, int(dim * 7 / 8)):
        idx = TunedGraphIndex(replace(base, pca_dim=d_r)).fit(data)
        r, qps = _measure(idx, queries, ti)
        rows_a.append(["pca", d_r, round(r, 4), f"{qps:.1f}",
                       f"x{qps / qps0:.2f}"])
    print_table("Fig.3a PCA dim", ["method", "D", "recall", "QPS", "vs"],
                rows_a)
    save("fig3a_pca", rows_a)

    rows_b = [["vanilla", 1.0, round(r0, 4), f"{qps0:.1f}", "x1.00"]]
    for alpha in (0.95, 0.9, 0.8, 0.7):
        idx = TunedGraphIndex(replace(base, antihub_keep=alpha)).fit(data)
        r, qps = _measure(idx, queries, ti)
        rows_b.append(["antihub", alpha, round(r, 4), f"{qps:.1f}",
                       f"x{qps / qps0:.2f}"])
    print_table("Fig.3b AntiHub alpha",
                ["method", "alpha", "recall", "QPS", "vs"], rows_b)
    save("fig3b_antihub", rows_b)

    # (c): same graph, only the entry-point selector changes
    rows_c = []
    from repro.core.entry_points import fit_entry_points
    for kc in (1, 8, 32, 128):
        eps = fit_entry_points(jax.random.PRNGKey(0), vanilla.base, kc)
        vanilla.eps = eps
        d, i = vanilla.search(queries, K)
        r = recall_at_k(i, ti)
        qps = measure_qps(lambda q: vanilla.search(q, K)[0], queries)
        q_p = vanilla.project(queries)
        _, _, hops = beam_search(q_p, vanilla.base,
                                 vanilla.graph.neighbors,
                                 eps.select(q_p), ef=64, k=K,
                                 gather_dist=dot_gather_dist)
        rows_c.append([kc, round(r, 4), f"{qps:.1f}",
                       f"x{qps / qps0:.2f}", float(np.mean(hops))])
    print_table("Fig.3c entry points",
                ["k", "recall", "QPS", "vs", "mean_hops"], rows_c)
    save("fig3c_entry_points", rows_c)
    return rows_a, rows_b, rows_c


if __name__ == "__main__":
    run()
