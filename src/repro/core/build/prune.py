"""α-RNG occlusion pruning + rebuild-free ``reprune`` (Zhang et al.,
"Prune, Don't Rebuild").

``alpha_prune`` generalizes NSG's MRNG edge-selection rule: scanning a
node's candidate pool nearest-first, candidate q is kept unless some
already-kept r occludes it — ``d(r, q) < alpha * d(p, q)`` (squared
distances; ``alpha`` therefore scales squared space). ``alpha = 1``
reproduces the MRNG rule bit-for-bit; larger ``alpha`` occludes more
aggressively, yielding sparser graphs that search faster at lower recall.

The key consequence (the "prune, don't rebuild" property): the greedy scan
only ever tests a candidate against *earlier-kept* candidates, so

  * pruning the same pool at a smaller ``degree`` returns exactly the first
    ``degree`` survivors of the max-degree scan (a prefix), and
  * re-scanning a pruned adjacency list at ``alpha = 1`` keeps every edge
    (each survivor was certified non-occluded by exactly its predecessors).

``reprune`` exploits both: a family of (alpha, degree) graphs is *derived*
from one cached max-degree graph with O(N * R) gather-distances + one
vmapped occlusion pass — no candidate pools, no beam searches, no rebuild.
This is what lets the tuner treat ``graph_degree`` and ``alpha`` as cheap
runtime knobs (the paper's §5.3 limitation).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.distances import match_vma


@jax.jit
def pairwise_rows_sqdist(q: jax.Array, data: jax.Array,
                         ids: jax.Array) -> jax.Array:
    """(B, D) queries vs per-row gathered ids (B, K) -> (B, K) sq dists."""
    rows = data[jnp.maximum(ids, 0)].astype(jnp.float32)       # (B, K, D)
    q32 = q.astype(jnp.float32)[:, None, :]
    d = jnp.sum((rows - q32) ** 2, axis=-1)
    return jnp.where(ids >= 0, d, jnp.inf)


def rows_sqdist_in_chunks(data: jax.Array, ids: jax.Array,
                          chunk: int = 2048) -> jax.Array:
    """Chunked ``pairwise_rows_sqdist`` of row i vs its (N, K) id table.

    The one gather-distance driver shared by every O(N * K) pass in the
    build stack (sorted adjacencies, union distances, the finish pass).
    """
    outs = []
    for s in range(0, ids.shape[0], chunk):
        e = min(s + chunk, ids.shape[0])
        outs.append(pairwise_rows_sqdist(data[s:e], data, ids[s:e]))
    return jnp.concatenate(outs)


def _alpha_scan(data, node_ids, cand_ids, cand_dists, degree, alpha):
    """The greedy α-RNG occlusion scan, vmapped over a node block.

    Returns (keep (B, degree) ids, kept_mask (B, L) bool) — the mask marks
    the candidate *positions* that survived, the compact encoding the
    memory-lean ``reprune_family`` stores instead of id stacks.
    """
    L = cand_ids.shape[1]

    def prune_one(p, c_ids, c_d):
        # under shard_map the fori carry must vary over the mesh axes like
        # the loop body's outputs do: type the constants after the inputs
        refs = (data, p, c_ids, c_d)
        keep = match_vma(jnp.full((degree,), -1, jnp.int32), *refs)
        kept_vecs = match_vma(
            jnp.zeros((degree, data.shape[1]), jnp.float32), *refs)
        mask = match_vma(jnp.zeros((L,), bool), *refs)

        def body(j, state):
            keep, kept_vecs, mask, cnt = state
            q = c_ids[j]
            dq = c_d[j]
            qv = data[jnp.maximum(q, 0)].astype(jnp.float32)
            dr = jnp.sum((kept_vecs - qv) ** 2, axis=-1)       # (degree,)
            occupied = jnp.arange(degree) < cnt
            occluded = jnp.any(occupied & (dr < alpha * dq))
            dup = jnp.any(occupied & (keep == q))
            ok = ((q >= 0) & (q != p) & (cnt < degree)
                  & (~occluded) & (~dup))
            slot = jnp.minimum(cnt, degree - 1)
            keep = jnp.where(ok, keep.at[slot].set(q), keep)
            kept_vecs = jnp.where(ok, kept_vecs.at[slot].set(qv), kept_vecs)
            mask = mask.at[j].set(ok)
            return keep, kept_vecs, mask, cnt + ok.astype(jnp.int32)

        keep, _, mask, _ = jax.lax.fori_loop(
            0, L, body, (keep, kept_vecs, mask,
                         match_vma(jnp.int32(0), *refs)))
        return keep, mask

    return jax.vmap(prune_one)(node_ids, cand_ids, cand_dists)


@functools.partial(jax.jit, static_argnames=("degree",))
def alpha_prune(data: jax.Array, node_ids: jax.Array, cand_ids: jax.Array,
                cand_dists: jax.Array, degree: int,
                alpha: float = 1.0) -> jax.Array:
    """α-RNG edge selection for a block of nodes.

    node_ids: (B,); cand_ids/cand_dists: (B, L) distance-ascending candidate
    pools (-1 padded). Returns (B, degree) pruned neighbor ids.

    Rule: scanning candidates nearest-first, keep q unless some already-kept
    r has d(r, q) < alpha * d(p, q). alpha=1 is exactly the MRNG occlusion
    test (the monotonic-graph property); alpha is applied to squared
    distances.
    """
    return _alpha_scan(data, node_ids, cand_ids, cand_dists, degree,
                       alpha)[0]


@functools.partial(jax.jit, static_argnames=("degree",))
def alpha_prune_mask(data: jax.Array, node_ids: jax.Array,
                     cand_ids: jax.Array, cand_dists: jax.Array,
                     degree: int, alpha: float = 1.0) -> jax.Array:
    """``alpha_prune``'s survivors as a (B, L) bool position mask.

    The same greedy scan — the ids ``alpha_prune`` returns are exactly
    ``cand_ids`` at the True positions, in order. A mask row plus the
    shared candidate pool reconstructs every degree prefix, which is what
    lets the reprune grid store one machine word per (alpha, node).
    """
    return _alpha_scan(data, node_ids, cand_ids, cand_dists, degree,
                       alpha)[1]


def prune_in_chunks(data, node_ids, cand_ids, cand_dists, degree, chunk,
                    alpha: float = 1.0):
    """Chunked driver for ``alpha_prune`` (bounds the vmapped block size)."""
    outs = []
    for s in range(0, node_ids.shape[0], chunk):
        e = min(s + chunk, node_ids.shape[0])
        outs.append(alpha_prune(data, node_ids[s:e], cand_ids[s:e],
                                cand_dists[s:e], degree, alpha))
    return jnp.concatenate(outs)


@jax.jit
def sorted_adjacency_chunk(data: jax.Array, rows: jax.Array,
                           neighbors: jax.Array):
    """One row chunk's adjacency as distance-ascending pools (ids, dists).

    ``rows`` are the chunk's own vectors (``data[s:e]``); the gather runs
    against the full ``data``. The streaming building block: callers that
    fuse sort + scan per chunk never hold more than a ``(chunk, R)`` f32
    block, whatever N is.
    """
    d = pairwise_rows_sqdist(rows, data, neighbors)
    order = jnp.argsort(d, axis=1, stable=True)
    return (jnp.take_along_axis(neighbors, order, axis=1),
            jnp.take_along_axis(d, order, axis=1))


def sorted_adjacency(data: jax.Array, neighbors: jax.Array,
                     chunk: int = 2048):
    """Adjacency rows as distance-ascending candidate pools (ids, dists).

    Materializes the full (N, R) f32 table — the small-N/parity form.
    Out-of-core callers stream ``sorted_adjacency_chunk`` instead.
    """
    d = rows_sqdist_in_chunks(data, neighbors, chunk)
    order = jnp.argsort(d, axis=1, stable=True)
    return (jnp.take_along_axis(neighbors, order, axis=1),
            jnp.take_along_axis(d, order, axis=1))


def reprune(data: jax.Array, neighbors: jax.Array, *, alpha: float = 1.0,
            degree: Optional[int] = None, chunk: int = 2048) -> jax.Array:
    """Derive an (alpha, degree) adjacency from a cached max-degree one.

    ``neighbors`` is an (N, R_max) pruned adjacency (e.g. the alpha=1
    max-degree graph a build cached). Cost: O(N * R) gather-distances + the
    occlusion scan — orders of magnitude below a rebuild. With alpha=1 and
    degree=R the result is bit-identical to pruning the original candidate
    pools at degree R (the prefix property; tier-1 tested).

    Streamed: each chunk's sort + occlusion scan runs fused, so the
    per-structure (N, R) f32 distance table never materializes — the
    float peak is (chunk, R) and the output is the (N, degree) int32
    adjacency the caller needs anyway. Row-independent, hence
    bit-identical to the materialized two-pass form.
    """
    n, rmax = neighbors.shape
    degree = rmax if degree is None else min(degree, rmax)
    node_ids = jnp.arange(n, dtype=jnp.int32)
    outs = []
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        cand_i, cand_d = sorted_adjacency_chunk(data, data[s:e],
                                                neighbors[s:e])
        outs.append(alpha_prune(data, node_ids[s:e], cand_i, cand_d,
                                degree, alpha))
    return jnp.concatenate(outs)


@jax.jit
def _pack_mask(mask: jax.Array) -> jax.Array:
    """(..., L) bool survivor mask -> (..., ceil(L/32)) uint32 words."""
    l = mask.shape[-1]
    w = -(-l // 32)
    m = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, w * 32 - l)])
    weights = jnp.left_shift(jnp.uint32(1),
                             jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(m.reshape(m.shape[:-1] + (w, 32)).astype(jnp.uint32)
                   * weights, axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("degree",))
def _family_member(cand_ids: jax.Array, masks_a: jax.Array,
                   degree: int) -> jax.Array:
    """Unpack one alpha's survivor bitmask into its (N, degree) member.

    ``rank <= degree`` realizes the prefix property: the degree-d member
    is the first d survivors of the max-degree scan, so one mask serves
    every degree.
    """
    n, rmax = cand_ids.shape
    pos = jnp.arange(rmax)
    word = masks_a[:, pos // 32]                               # (N, R)
    bits = (jnp.right_shift(word, (pos % 32).astype(jnp.uint32))
            & jnp.uint32(1)) != 0
    rank = jnp.cumsum(bits.astype(jnp.int32), axis=1)
    take = bits & (rank <= degree)
    slot = jnp.where(take, rank - 1, degree)    # overflow col, sliced off
    rows = jnp.arange(n)[:, None]
    out = jnp.full((n, degree + 1), -1, jnp.int32
                   ).at[rows, slot].set(jnp.where(take, cand_ids, -1))
    return out[:, :degree]


class RepruneFamily:
    """Memory-lean (alpha, degree) reprune grid: packed survivor bitmasks.

    Instead of the (A, N, R) int32 member stack (~9 * N * R * 4 bytes —
    ~11 GB at 10M nodes), stores one uint32 word per (alpha, node, 32
    candidates) — an ``(A, N, ceil(R/32))`` array, i.e. effectively
    (A, N) for R <= 32 — against the ONE shared distance-ascending
    max-degree adjacency. ``member(a_idx, degree)`` reconstructs any grid
    member lazily in one unpack pass, bit-identical to the materialized
    stack slice (tier-1 asserted).
    """

    def __init__(self, alphas, cand_ids: jax.Array, masks: jax.Array):
        self.alphas = tuple(float(a) for a in alphas)
        self.cand_ids = cand_ids     # (N, R) sorted max-degree adjacency
        self.masks = masks           # (A, N, W) uint32 survivor bits

    @property
    def shape(self):
        n, rmax = self.cand_ids.shape
        return (len(self.alphas), n, rmax)

    def nbytes(self) -> int:
        """Grid storage beyond the shared adjacency (the lean part)."""
        return int(self.masks.size) * 4

    def member(self, a_idx: int, degree: Optional[int] = None) -> jax.Array:
        """(N, degree) ids == ``reprune(..., alpha=alphas[a_idx], degree)``."""
        rmax = self.cand_ids.shape[1]
        degree = rmax if degree is None else min(degree, rmax)
        return _family_member(self.cand_ids, self.masks[a_idx], degree)

    def materialize(self) -> jax.Array:
        """The full (A, N, R) stack (tests / small-N compat)."""
        return jnp.stack([self.member(i) for i in range(len(self.alphas))])


def reprune_family(data: jax.Array, neighbors: jax.Array, alphas,
                   chunk: int = 2048, materialize: bool = True):
    """The whole Pareto-relevant (alpha, degree) grid in ONE vmapped pass.

    Every alpha shares the same distance-ascending candidate pool (the
    sorted max-degree adjacency — computed once), so the A-point alpha
    grid is a ``vmap`` of the occlusion scan over the alpha axis; and a
    smaller ``degree`` is a *prefix* of the max-degree scan (the greedy
    rule only ever tests a candidate against earlier-kept ones), so no
    degree axis is materialized at all. With ``materialize=True`` returns
    an (A, N, R_max) stack:

        stack[i, :, :d]  ==  reprune(data, neighbors, alpha=alphas[i],
                                     degree=d)          # bit-identical

    making every (alpha, degree) trial a lookup + slice. With
    ``materialize=False`` returns a ``RepruneFamily`` holding only the
    packed (A, N, ceil(R/32)) uint32 survivor bitmasks — ~R x leaner, the
    form that scales to 10M nodes — whose ``member(i, d)`` reconstructs
    the same arrays bit-identically on demand.
    """
    n, rmax = neighbors.shape
    node_ids = jnp.arange(n, dtype=jnp.int32)
    al = jnp.asarray(alphas, jnp.float32)
    outs, cand_parts = [], []
    # streamed like `reprune`: each chunk's sorted pools feed the vmapped
    # alpha axis immediately, so the (N, R) f32 table never materializes
    # — only the int32 adjacency (and, lean path, the packed masks)
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        ci, cd = sorted_adjacency_chunk(data, data[s:e], neighbors[s:e])
        cand_parts.append(ci)
        if materialize:
            outs.append(jax.vmap(
                lambda a, ci=ci, cd=cd, s=s, e=e: alpha_prune(
                    data, node_ids[s:e], ci, cd, rmax, a))(al))
        else:
            outs.append(_pack_mask(jax.vmap(
                lambda a, ci=ci, cd=cd, s=s, e=e: alpha_prune_mask(
                    data, node_ids[s:e], ci, cd, rmax, a))(al)))
    stacked = jnp.concatenate(outs, axis=1)
    if materialize:
        return stacked
    return RepruneFamily(alphas, jnp.concatenate(cand_parts), stacked)


def nsg_from_neighbors(data: jax.Array, neighbors: jax.Array, medoid, *,
                       knn_ids: Optional[jax.Array] = None,
                       finish_backend: str = "auto"):
    """Pruned adjacency -> servable ``NSGGraph`` (connectivity repair).

    The shared tail of every rebuild-free derivation path: ``reprune_nsg``
    and the tuner's ``reprune_family`` lookups both end here. ``knn_ids``
    supplies repair parents (the build-time kNN table if the caller kept
    it; defaults to the adjacency itself); ``finish_backend`` selects the
    repair implementation (``core/build/finish.py`` — device batched
    rounds by default, the host BFS loop for parity).
    """
    from repro.core.build.finish import repair
    from repro.core.nsg import NSGGraph

    parents = knn_ids if knn_ids is not None else neighbors
    nbrs, _ = repair(data, neighbors, medoid, parents,
                     backend=finish_backend)
    return NSGGraph(neighbors=jnp.asarray(nbrs), medoid=jnp.asarray(
        medoid, jnp.int32))


def reprune_nsg(data: jax.Array, graph, *, alpha: float = 1.0,
                degree: Optional[int] = None,
                knn_ids: Optional[jax.Array] = None, chunk: int = 2048,
                finish_backend: str = "auto"):
    """``reprune`` + NSG connectivity repair -> a servable ``NSGGraph``.

    ``knn_ids`` supplies repair parents (the build-time kNN table if the
    caller kept it; defaults to the cached adjacency itself).
    """
    nbrs = reprune(data, graph.neighbors, alpha=alpha, degree=degree,
                   chunk=chunk)
    return nsg_from_neighbors(data, nbrs, graph.medoid, knn_ids=knn_ids,
                              finish_backend=finish_backend)
