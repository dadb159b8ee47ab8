"""NSG graph construction (Fu et al., VLDB'19) adapted to batched JAX.

Build phases:
  1. medoid (navigating node) — one distance pass;
  2. per-node candidate pools, two backends (``pools_backend``):
     * ``"search"`` — beam search *on the kNN graph* toward each node,
       union its kNN list (all batched/vmapped, chunked over nodes) — the
       classic NSG recipe, O(hops * K) distance evals per node: the build
       wall-clock ceiling at large N;
     * ``"nndescent"`` — pools derived from the kNN *table* itself
       (forward ∪ reverse ∪ 1-hop expansion, ``core/build/pools.py``),
       O(K * fanout) evals per node. The default whenever the table's
       distances are available (i.e. the kNN backend was NN-Descent or
       handed its dists through); the beam-search pools remain as the
       fallback and as the parity baseline.
  3. MRNG occlusion pruning — the sequential heap walk becomes a fixed-length
     masked fori_loop vmapped over nodes (O(L * R) distance checks per node,
     all MXU matmuls);
  4. reverse-edge interconnect + re-prune (``core/build/finish.py``,
     selected by ``finish_backend``: the device path accumulates reverse
     edges by salted scatter-min and dedups the union through
     ``kernels/topk_merge``; the host path keeps the original ragged
     append as the parity baseline);
  5. connectivity repair — reachability + batched attach of unreachable
     nodes beneath their nearest reachable kNN parent (device: vectorized
     frontier propagation + one-attach-per-parent rounds; host: the
     original numpy BFS loop).

With ``finish_backend="device"`` (what ``"auto"`` resolves to) every
phase runs on device as fixed-shape jitted ops — no host round-trip
between the candidate pools and the final servable graph.
``build_nsg(with_stats=True)`` returns an ``NSGBuildStats`` whose
``pool_evals`` counts phase 2's database-distance evaluations exactly —
the quantity the pools backends compete on — and whose
``interconnect_seconds`` / ``repair_seconds`` / ``repair_rounds`` time
the finishing stages the finish backends compete on.

The pruning primitive itself lives in ``core/build/prune.py`` as the α-RNG
rule (``alpha_prune``); ``mrng_prune`` below is its alpha=1 specialization,
kept as the historical name. ``build_nsg(alpha=...)`` passes the knob
through, and ``build.prune.reprune`` derives sparser (alpha, degree)
variants from a built graph with no rebuild.
"""
from __future__ import annotations

import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.beam_search import beam_search, dot_gather_dist
from repro.core.build.finish import finish_nsg, resolve_finish_backend
from repro.core.build.pools import nnd_candidate_pools
from repro.core.build.prune import (
    alpha_prune, pairwise_rows_sqdist, prune_in_chunks,
    rows_sqdist_in_chunks,
)
from repro.core.distances import nearest
from repro.kernels.topk_merge import topk_pool


class NSGGraph(NamedTuple):
    neighbors: jax.Array   # (N, R) int32, -1 padded
    medoid: jax.Array      # () int32


class NSGBuildStats(NamedTuple):
    """Work accounting for one NSG build."""
    pools_backend: str     # "search" | "nndescent" (resolved)
    n: int
    degree: int
    pool_evals: int        # phase-2 database-distance evaluations
    prune_evals: int       # phases 3-4, derived from the ACTUAL pool and
    # union widths (a capped reverse buffer or changed n_candidates is
    # reflected, never silently desynced from a hardcoded formula)
    finish_backend: str = "host"    # "host" | "device" (resolved)
    interconnect_seconds: float = 0.0   # phase-4 wall-clock (to ready)
    repair_seconds: float = 0.0         # phase-5 wall-clock (to ready)
    repair_rounds: int = 0              # attach rounds until reachable
    pools_seconds: float = 0.0          # phase-2 wall-clock (to ready)
    prune_seconds: float = 0.0          # phase-3 wall-clock (to ready)


POOLS_BACKENDS = ("search", "nndescent", "auto")


def resolve_pools_backend(backend: str, knn_dists) -> str:
    """Resolve ``"auto"``: table-derived pools whenever dists are in hand."""
    if backend not in POOLS_BACKENDS:
        raise ValueError(
            f"unknown pools backend {backend!r}; expected one of "
            f"{POOLS_BACKENDS}")
    if backend == "auto":
        return "nndescent" if knn_dists is not None else "search"
    return backend


def mrng_prune(data: jax.Array, node_ids: jax.Array, cand_ids: jax.Array,
               cand_dists: jax.Array, degree: int) -> jax.Array:
    """MRNG edge selection — ``alpha_prune`` at alpha=1 (bit-identical)."""
    return alpha_prune(data, node_ids, cand_ids, cand_dists, degree)


# ---------------------------------------------------------------------------
# Candidate pools
# ---------------------------------------------------------------------------


def _candidate_pools(data, knn_ids, medoid, n_candidates, chunk,
                     merge_backend=None):
    """Per-node candidate pools: beam-search the kNN graph toward each node,
    then union the node's own kNN list. Returns (N, L) ids + dists sorted
    plus the distance-evaluation count (hops * K expansions + the entry
    distance + the own-list pass, per node)."""
    n, k = knn_ids.shape
    ef = n_candidates
    pools_i, pools_d, hops_parts = [], [], []
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        q = data[s:e]
        entry = jnp.full((e - s,), medoid, jnp.int32)
        d_pool, i_pool, hops = beam_search(
            q, data, knn_ids, entry, ef=ef, k=ef, max_iters=2 * ef,
            mode="while", gather_dist=dot_gather_dist)
        own = knn_ids[s:e]                                     # (b, k)
        own_d = pairwise_rows_sqdist(q, data, own)
        hops_parts.append(hops)        # summed host-side AFTER the loop:
        # an int() here would sync per chunk and serialize the dispatch
        ids = jnp.concatenate([i_pool, own], axis=1)
        ds = jnp.concatenate([d_pool, own_d], axis=1)
        # dedup: first occurrence (the nearest copy) wins
        ids, ds = topk_pool(ids, ds, ef, backend=merge_backend)
        pools_i.append(ids)
        pools_d.append(ds)
    evals = sum(int(np.sum(np.asarray(h), dtype=np.int64)) * k
                for h in hops_parts) + n * (k + 1)
    return jnp.concatenate(pools_i), jnp.concatenate(pools_d), evals


# ---------------------------------------------------------------------------
# Build
# ---------------------------------------------------------------------------


def build_nsg(data: jax.Array, knn_ids: jax.Array, *, degree: int,
              n_candidates: int = 64, chunk: int = 2048,
              alpha: float = 1.0, pools_backend: str = "auto",
              knn_dists: Optional[jax.Array] = None,
              finish_backend: str = "auto",
              rev_cap: Optional[int] = None,
              merge_backend: Optional[str] = None,
              with_stats: bool = False):
    """Build an NSG over ``data`` from its kNN graph.

    ``pools_backend`` picks phase 2: ``"search"`` (beam-search pools, the
    classic recipe), ``"nndescent"`` (table-derived pools — requires or
    recomputes ``knn_dists``), or ``"auto"`` (table-derived whenever
    ``knn_dists`` is provided). ``finish_backend`` picks phases 4-5
    (``core/build/finish.py``): ``"device"`` — scatter-min reverse
    interconnect + batched repair, fixed-shape jitted (what ``"auto"``
    resolves to); ``"host"`` — the original numpy path, the parity
    baseline. ``rev_cap`` bounds the reverse buffer (default 2 * degree).
    ``merge_backend`` pins the ``kernels/topk_merge`` primitive behind
    every sort/dedup in the build — phase-2 pool assembly AND the
    finishing pass — (None = platform default: Pallas on TPU, jnp
    elsewhere). Returns the ``NSGGraph`` — plus an ``NSGBuildStats`` when
    ``with_stats`` is set.
    """
    n = data.shape[0]
    resolved = resolve_pools_backend(pools_backend, knn_dists)
    resolved_finish = resolve_finish_backend(finish_backend)
    mean = jnp.mean(data.astype(jnp.float32), axis=0, keepdims=True)
    _, medoid = nearest(mean, data)
    medoid = medoid[0].astype(jnp.int32)

    t_pools = time.perf_counter()
    if resolved == "nndescent":
        if knn_dists is None:
            # explicit request without table dists: one O(N*K) gather pass
            knn_dists = rows_sqdist_in_chunks(data, knn_ids, chunk)
            pool_evals = int(n) * int(knn_ids.shape[1])
        else:
            pool_evals = 0
        cand_i, cand_d, ev = nnd_candidate_pools(
            data, knn_ids, knn_dists, n_candidates, chunk=chunk,
            merge_backend=merge_backend)
        pool_evals += ev
    else:
        cand_i, cand_d, pool_evals = _candidate_pools(
            data, knn_ids, medoid, n_candidates, chunk, merge_backend)
    if with_stats:
        jax.block_until_ready(cand_d)   # to-ready, like the finish timings
    t_prune = time.perf_counter()
    pools_seconds = t_prune - t_pools
    node_ids = jnp.arange(n, dtype=jnp.int32)
    nbrs = prune_in_chunks(data, node_ids, cand_i, cand_d, degree, chunk,
                           alpha)
    if with_stats:
        jax.block_until_ready(nbrs)
    prune_seconds = time.perf_counter() - t_prune

    # --- finishing pass: reverse interconnect + connectivity repair ---
    nbrs, fstats = finish_nsg(
        data, nbrs, medoid, knn_ids, degree=degree, alpha=alpha,
        chunk=chunk, backend=resolved_finish, rev_cap=rev_cap,
        merge_backend=merge_backend)
    graph = NSGGraph(neighbors=jnp.asarray(nbrs), medoid=medoid)
    if with_stats:
        # fixed-shape occlusion + interconnect work, identical across
        # pools backends and DERIVED from the widths actually built:
        # phase-3 scan (L * degree per node), the union distance pass
        # (what the finish backend actually issued — the device path
        # reuses forward distances for reverse edges), the phase-4
        # re-prune (union_width * degree per node)
        prune_evals = (n * cand_i.shape[1] * degree
                       + fstats.union_dist_evals
                       + n * fstats.union_width * degree)
        return graph, NSGBuildStats(
            pools_backend=resolved, n=n, degree=degree,
            pool_evals=int(pool_evals), prune_evals=int(prune_evals),
            finish_backend=fstats.backend,
            interconnect_seconds=fstats.interconnect_seconds,
            repair_seconds=fstats.repair_seconds,
            repair_rounds=fstats.repair_rounds,
            pools_seconds=pools_seconds,
            prune_seconds=prune_seconds)
    return graph
