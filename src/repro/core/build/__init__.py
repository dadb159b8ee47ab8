"""Device-resident approximate graph-build subsystem.

One entry point for every build-time kNN-graph consumer (pipeline, antihub,
factory builds, sharded builds, launchers):

    dists, ids = build_knn(data, k, backend="exact" | "nndescent" | "auto")

``exact`` is the O(N^2 D) chunked streaming pass (``core/knn_graph``);
``nndescent`` is the batched NN-Descent refinement (``build/nn_descent``)
that issues orders of magnitude fewer distance evaluations at scale;
``auto`` picks NN-Descent once N crosses ``AUTO_NND_MIN_N`` (below it the
exact pass is both faster in wall-clock and free of approximation).

``build/prune.py`` holds the complementary search-graph side: the α-RNG
occlusion primitive (``alpha_prune``, MRNG at alpha=1) and the
rebuild-free ``reprune`` family derivation; ``build/finish.py`` the NSG
finishing pass (reverse interconnect + connectivity repair) with its own
``finish_backend`` device/host selection.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.core.build.finish import (
    FINISH_BACKENDS, FinishStats, finish_nsg, reachable_mask, repair,
    repair_connectivity_device, resolve_finish_backend,
)
from repro.core.build.nn_descent import BuildStats, nn_descent
from repro.core.build.pools import nnd_candidate_pools
from repro.core.build.prune import (
    RepruneFamily, alpha_prune, alpha_prune_mask,
    nsg_from_neighbors, pairwise_rows_sqdist, prune_in_chunks, reprune,
    reprune_family, reprune_nsg, rows_sqdist_in_chunks, sorted_adjacency,
    sorted_adjacency_chunk,
)
from repro.core.build.shardlocal import derive_local, repair_local
from repro.core.build.stream import (
    DEFAULT_CHUNK, HostOffloadStore, chunk_spans,
)

__all__ = [
    "AUTO_NND_MIN_N", "BuildStats", "DEFAULT_CHUNK", "FINISH_BACKENDS",
    "FinishStats", "HostOffloadStore", "RepruneFamily", "alpha_prune",
    "alpha_prune_mask", "build_knn", "chunk_spans", "derive_local",
    "finish_nsg", "knn_graph_recall", "nn_descent",
    "nnd_candidate_pools", "nsg_from_neighbors", "pairwise_rows_sqdist",
    "prune_in_chunks", "reachable_mask", "repair",
    "repair_connectivity_device", "repair_local", "reprune",
    "reprune_family", "reprune_nsg", "resolve_backend",
    "resolve_finish_backend", "rows_sqdist_in_chunks", "sorted_adjacency",
    "sorted_adjacency_chunk",
]


def knn_graph_recall(approx_ids, exact_ids) -> float:
    """Mean overlap between an approximate and the exact kNN id table.

    -1 padding never counts as a hit; the denominator is the number of
    valid exact entries. The one definition shared by the tier-1
    acceptance tests and the BENCH_build benchmark, so "recall >= 0.9"
    means the same thing in both.
    """
    import numpy as np
    approx_ids = np.asarray(approx_ids)
    exact_ids = np.asarray(exact_ids)
    hits, valid = 0, 0
    for row in range(exact_ids.shape[0]):
        true_set = exact_ids[row][exact_ids[row] >= 0]
        got = approx_ids[row][approx_ids[row] >= 0]
        hits += len(np.intersect1d(got, true_set))
        valid += len(true_set)
    return hits / max(valid, 1)

# Below this N the exact pass wins on wall-clock (one matmul sweep, no
# refinement rounds) and is exact for free; above it, NN-Descent's
# sub-quadratic distance-evaluation count dominates.
AUTO_NND_MIN_N = 8192

_BACKENDS = ("exact", "nndescent", "auto")


def resolve_backend(backend: str, n: int) -> str:
    """Resolve ``"auto"`` against the database size; validate the name."""
    if backend not in _BACKENDS:
        raise ValueError(
            f"unknown knn backend {backend!r}; expected one of {_BACKENDS}")
    if backend == "auto":
        return "nndescent" if n >= AUTO_NND_MIN_N else "exact"
    return backend


def build_knn(data: jax.Array, k: int, *, backend: str = "auto",
              key: Optional[jax.Array] = None, with_stats: bool = False,
              **kw):
    """Build the (N, k) kNN graph with the selected backend.

    Returns (dists, ids) like ``knn_graph`` — plus a ``BuildStats`` when
    ``with_stats`` is set. Extra keyword args reach the backend (chunk
    sizes for exact, rounds/sampling for NN-Descent).
    """
    from repro.core.knn_graph import knn_graph   # lazy: avoids import cycle

    n = data.shape[0]
    resolved = resolve_backend(backend, n)
    if backend == "auto" and kw:
        # under auto the caller can't know which backend runs: silently
        # drop kwargs the resolved backend doesn't accept instead of
        # crashing in a data-size-dependent way
        import inspect
        fn = knn_graph if resolved == "exact" else nn_descent
        accepted = set(inspect.signature(fn).parameters)
        kw = {k_: v for k_, v in kw.items() if k_ in accepted}
    if resolved == "exact":
        d, i = knn_graph(data, k, **kw)
        if with_stats:
            return d, i, BuildStats(backend="exact", n=n, k=k,
                                    distance_evals=n * n, rounds=1,
                                    update_rate=0.0)
        return d, i
    return nn_descent(data, k, key=key, with_stats=with_stats, **kw)
