"""Build-backend scaling: seconds + distance-evaluation counts per backend
per N -> ``BENCH_build.json`` at the repo root (CI uploads it next to
BENCH_qps.json, the accumulating build-cost trajectory).

Three comparisons land in the artifact:

  * ``stage="knn"`` — exact O(N^2) kNN construction vs batched NN-Descent
    (the PR-3 gap: orders of magnitude fewer evaluations at scale);
  * ``stage="nsg_pools"`` — NSG candidate pools by beam search
    (``pools_backend="search"``) vs derived from the kNN table
    (``pools_backend="nndescent"``): the pool phase was the remaining
    build ceiling past ~20k nodes; the table-derived pools make the whole
    build path sub-quadratic. Each point carries ``pool_evals`` and the
    resulting graph's recall@10 so the ≥5x eval drop at matched recall is
    visible in CI history.
  * ``stage="nsg_finish"`` — the finishing pass (reverse interconnect +
    connectivity repair) on device (``finish_backend="device"``: salted
    scatter-min reverse buffer, topk_merge union dedup, batched repair
    rounds) vs the host numpy path. Each point carries
    ``interconnect_seconds``, ``repair_seconds`` (their sum is
    ``seconds``), ``repair_rounds`` and the graph's recall@10 — the host
    O(N * R) pointer loops were the last non-device stage, and the
    device advantage at the largest measured N is the PR-5 acceptance
    number.

Wall-clock on the 1-core CI box still favors the exact matmul sweep at
small N — which is exactly why ``knn_backend="auto"`` switches on N, and
why both numbers land in the artifact.

Scale via ``BENCH_BUILD_NS`` (comma-separated Ns) and BENCH_DIM/BENCH_Q;
``BENCH_BUILD_SLOW_N`` appends one NN-Descent-only point (no exact kNN
baseline, no search pools — at that scale neither terminates in CI time:
that is the new ceiling the artifact documents) plus the host-vs-device
``nsg_finish`` pair at that N (the host finish still terminates — it is
merely slow, which is the point being measured). The CI bench-smoke runs
a tiny instance of exactly this file and fails if the
``pools_backend="nndescent"`` or ``stage="nsg_finish"`` points are
missing.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import DIM, N_QUERIES, print_table, save, \
    save_bench_json
from repro.core.beam_search import beam_search, dot_gather_dist
from repro.core.build import build_knn, knn_graph_recall
from repro.core.flat import FlatIndex, recall_at_k
from repro.core.nsg import build_nsg
from repro.data import clustered_vectors, queries_like

NS = tuple(int(s) for s in os.environ.get(
    "BENCH_BUILD_NS", "2000,5000,10000").split(",") if s.strip())
K = int(os.environ.get("BENCH_BUILD_K", 10))
NSG_DEGREE = int(os.environ.get("BENCH_BUILD_DEGREE", 16))
SLOW_N = int(os.environ.get("BENCH_BUILD_SLOW_N", 0))


def _graph_recall10(data, graph, queries, true_i):
    entry = jnp.full((queries.shape[0],), graph.medoid, jnp.int32)
    _, ids, _ = beam_search(queries, data, graph.neighbors, entry,
                            ef=64, k=10, gather_dist=dot_gather_dist)
    return float(recall_at_k(ids, true_i))


def _nsg_pool_points(n, data, knn_d, knn_i, queries, true_i, backends,
                     points, rows):
    """One build per pools backend; append stage="nsg_pools" points."""
    for pb in backends:
        t0 = time.perf_counter()
        graph, st = build_nsg(data, knn_i, degree=NSG_DEGREE,
                              n_candidates=2 * NSG_DEGREE,
                              pools_backend=pb, knn_dists=knn_d,
                              with_stats=True)
        jax.block_until_ready(graph.neighbors)
        secs = time.perf_counter() - t0
        rec = _graph_recall10(data, graph, queries, true_i)
        points.append({
            "n": n, "dim": DIM, "k": K, "stage": "nsg_pools",
            "degree": NSG_DEGREE, "pools_backend": st.pools_backend,
            "seconds": round(secs, 3), "pool_evals": st.pool_evals,
            "prune_evals": st.prune_evals,
            "nsg_recall_at_10": round(rec, 4),
        })
        rows.append([f"N={n} pools={pb}", f"{secs:.2f}s",
                     f"{st.pool_evals:.3g} pool evals",
                     f"recall@10 {rec:.4f}"])
    if len(backends) == 2:
        ratio = (points[-2]["pool_evals"] /
                 max(points[-1]["pool_evals"], 1))
        rows.append([f"N={n} pool-eval ratio", f"{ratio:.1f}x", "", ""])


def _nsg_finish_points(n, data, knn_d, knn_i, queries, true_i, points,
                       rows):
    """Finish ONE shared pre-finish adjacency per backend.

    Phases 1-3 (medoid, table-derived pools, occlusion prune — identical
    across finish backends, and the dominant build work) run once; each
    backend then finishes the very same pruned adjacency, so the
    stage="nsg_finish" pair isolates exactly the work being compared.

    Runs AFTER _nsg_pool_points at the same N on purpose: those builds
    (finish_backend default = device) compile the device finish kernels
    at this N's shapes, so the seconds measured here are warm-cache work,
    not XLA compile time — the same treatment the host path gets. The
    pools+prune pass here deliberately duplicates the one inside the
    pool-point builds (~1-2 min at the 100k slow point): build_nsg does
    not expose its pre-finish adjacency, and keeping its API free of
    bench-only outputs is worth the extra pass."""
    from repro.core.build import nnd_candidate_pools, prune_in_chunks
    from repro.core.build.finish import finish_nsg
    from repro.core.distances import nearest
    from repro.core.nsg import NSGGraph

    mean = jnp.mean(data.astype(jnp.float32), axis=0, keepdims=True)
    _, medoid = nearest(mean, data)
    medoid = medoid[0].astype(jnp.int32)
    cand_i, cand_d, _ = nnd_candidate_pools(data, knn_i, knn_d,
                                            2 * NSG_DEGREE)
    node_ids = jnp.arange(data.shape[0], dtype=jnp.int32)
    pre = prune_in_chunks(data, node_ids, cand_i, cand_d, NSG_DEGREE,
                          2048, 1.0)
    jax.block_until_ready(pre)
    finish_secs = {}
    for fb in ("host", "device"):
        nbrs, st = finish_nsg(data, pre, medoid, knn_i,
                              degree=NSG_DEGREE, backend=fb)
        secs = st.interconnect_seconds + st.repair_seconds
        finish_secs[fb] = secs
        graph = NSGGraph(neighbors=jnp.asarray(nbrs), medoid=medoid)
        rec = _graph_recall10(data, graph, queries, true_i)
        points.append({
            "n": n, "dim": DIM, "k": K, "stage": "nsg_finish",
            "degree": NSG_DEGREE, "finish_backend": st.backend,
            "seconds": round(secs, 3),
            "interconnect_seconds": round(st.interconnect_seconds, 3),
            "repair_seconds": round(st.repair_seconds, 3),
            "repair_rounds": st.repair_rounds,
            "nsg_recall_at_10": round(rec, 4),
        })
        rows.append([f"N={n} finish={fb}", f"{secs:.2f}s",
                     f"{st.repair_rounds} repair rounds",
                     f"recall@10 {rec:.4f}"])
    ratio = finish_secs["host"] / max(finish_secs["device"], 1e-9)
    rows.append([f"N={n} finish host/device", f"{ratio:.1f}x", "", ""])


def run():
    points, rows = [], []
    for n in NS:
        data = clustered_vectors(jax.random.PRNGKey(42), n, DIM,
                                 n_clusters=max(8, n // 400))
        queries = queries_like(jax.random.PRNGKey(43), data, N_QUERIES)
        _, true_i = FlatIndex(data).search(queries, 10)
        per_backend = {}
        knn_tables = {}
        for backend in ("exact", "nndescent"):
            t0 = time.perf_counter()
            d, ids, stats = build_knn(data, K, backend=backend,
                                      key=jax.random.PRNGKey(0),
                                      with_stats=True)
            jax.block_until_ready(ids)
            secs = time.perf_counter() - t0
            per_backend[backend] = np.asarray(ids)
            knn_tables[backend] = (d, ids)
            rec = (1.0 if backend == "exact" else
                   knn_graph_recall(per_backend["nndescent"],
                                    per_backend["exact"]))
            points.append({
                "n": n, "dim": DIM, "k": K, "stage": "knn",
                "backend": backend, "seconds": round(secs, 3),
                "distance_evals": stats.distance_evals,
                "rounds": stats.rounds,
                "knn_recall_vs_exact": round(float(rec), 4),
            })
            rows.append([f"N={n} {backend}", f"{secs:.2f}s",
                         f"{stats.distance_evals:.3g} evals",
                         f"recall {rec:.4f}"])
        ratio = (points[-2]["distance_evals"] /
                 max(points[-1]["distance_evals"], 1))
        rows.append([f"N={n} eval ratio", f"{ratio:.1f}x", "", ""])

        # the NSG pool phase on the NN-Descent table: beam-search pools
        # vs table-derived pools, same downstream pruning
        knn_d, knn_i = knn_tables["nndescent"]
        _nsg_pool_points(n, data, knn_d, knn_i, queries, true_i,
                         ("search", "nndescent"), points, rows)
        # the finishing pass (interconnect + repair), host vs device
        _nsg_finish_points(n, data, knn_d, knn_i, queries, true_i,
                           points, rows)

    if SLOW_N:
        # the new ceiling: NN-Descent kNN + table-derived pools only —
        # the quadratic baselines are deliberately absent at this N
        n = SLOW_N
        data = clustered_vectors(jax.random.PRNGKey(42), n, DIM,
                                 n_clusters=max(8, n // 400))
        queries = queries_like(jax.random.PRNGKey(43), data, N_QUERIES)
        _, true_i = FlatIndex(data).search(queries, 10)
        t0 = time.perf_counter()
        knn_d, knn_i, stats = build_knn(data, K, backend="nndescent",
                                       key=jax.random.PRNGKey(0),
                                       with_stats=True)
        jax.block_until_ready(knn_i)
        secs = time.perf_counter() - t0
        points.append({
            "n": n, "dim": DIM, "k": K, "stage": "knn",
            "backend": "nndescent", "seconds": round(secs, 3),
            "distance_evals": stats.distance_evals,
            "rounds": stats.rounds, "knn_recall_vs_exact": None,
        })
        rows.append([f"N={n} nndescent (slow)", f"{secs:.2f}s",
                     f"{stats.distance_evals:.3g} evals", ""])
        _nsg_pool_points(n, data, knn_d, knn_i, queries, true_i,
                         ("nndescent",), points, rows)
        # host finish still terminates at this N (unlike the quadratic
        # kNN/pool baselines) — measuring its gap to the device path at
        # the largest N is this stage's acceptance number
        _nsg_finish_points(n, data, knn_d, knn_i, queries, true_i,
                           points, rows)

    headers = ["config", "build time", "distance evals", "vs exact"]
    print_table("kNN-graph + NSG-pool build scaling", headers, rows)
    save("build_scaling", rows, headers)
    path = save_bench_json(
        "build", {"points": points},
        dataset={"ns": list(NS), "dim": DIM, "k": K,
                 "nsg_degree": NSG_DEGREE,
                 "slow_n": SLOW_N or None})
    print(f"wrote {path}")
    return points


if __name__ == "__main__":
    run()
