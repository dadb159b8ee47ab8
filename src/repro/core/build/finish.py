"""Device-resident NSG finishing pass: reverse interconnect + repair.

The NSG build's first three phases (kNN graph, candidate pools, occlusion
pruning) became device-resident and sub-quadratic in PRs 3/4; what remained
host-side were the two *finishing* stages — O(N * R) pointer work that
blocks the build path from scaling past ~50k nodes on the CI box:

  * the reverse-edge interconnect: a ragged Python append over every
    directed edge, truncated to a 2R cap per node;
  * connectivity repair: a numpy BFS from the medoid plus a sequential
    attach loop for unreachable nodes.

This module restates both as fixed-shape jitted programs, selected by
``finish_backend``:

  * ``"device"`` (what ``"auto"`` resolves to) —
      - reverse edges accumulate by *salted scatter-min* into a capped
        ``(N, rev_cap)`` slot buffer (the proposal-buffer idiom from
        ``nn_descent.py``): slot = salted multiplicative hash of the
        source id, nearest proposal per slot wins, collisions drop — the
        fixed-shape stand-in for ragged reverse lists. Reverse distances
        are the forward distances (L2 is symmetric), so the union costs
        one O(N * R) forward gather-distance pass, not O(N * U);
      - the forward ∪ reverse union sorts/dedups through
        ``kernels/topk_merge`` (``topk_pool``: nearest copy wins), so on
        TPU there is no host round-trip between the pools and the final
        pruned graph;
      - reachability is an iterative vectorized frontier propagation (one
        boolean scatter over the (N, R) adjacency per hop, early exit on
        fixpoint inside a ``while_loop``) replacing the host BFS;
      - repair attaches ALL unreachable nodes per round through a
        vectorized nearest-reachable-parent selection (first reachable
        kNN parent that can accept; exact nearest-reachable fallback for
        the rest), one attachment per parent per round resolved by
        scatter-min, with *protected-slot masking*: repair edges are
        never evicted, so repairs are monotone and rounds converge — the
        same invariant the host loop keeps via its ``protected`` dict.
  * ``"host"`` — the original numpy path, kept bit-for-bit as the parity
    baseline (the pinned 20k acceptance measurements build against it).

Batched repair differs from the sequential host loop only in *within-round*
chaining (the host marks a just-attached node reachable immediately; the
device path picks it up next round when reachability is recomputed) and in
tie order under the scatter salt — graph parity is therefore recall-level,
not bit-level, and is tier-1 tested as such.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.build.prune import prune_in_chunks, rows_sqdist_in_chunks
from repro.core.distances import match_vma
from repro.kernels.topk_merge import topk_pool
from repro.kernels.topk_merge.ref import mark_dups

FINISH_BACKENDS = ("host", "device", "auto")

# Fallback-parent blocks are padded to this many rows so the exact
# nearest-reachable pass (rare: only nodes with no reachable kNN parent)
# never retraces on the number of orphans.
_FB_BLOCK = 256

# Scatter-min slot oversampling: reverse edges hash into OVERSAMPLE *
# rev_cap slots before the nearest rev_cap are kept, so hash collisions
# (which drop whole edges, the one lossy step vs the host's compact
# append) cost ~1/OVERSAMPLE as much. Transient memory only.
_REV_OVERSAMPLE = 4

_SALT = np.uint32(0x9E3779B9)          # fixed: builds stay deterministic


class FinishStats(NamedTuple):
    """Work + wall-clock accounting for one finishing pass."""
    backend: str               # "host" | "device" (resolved)
    union_width: int           # forward + reverse union width actually built
    union_dist_evals: int      # distance evals the union pass issued
    interconnect_seconds: float
    repair_seconds: float
    repair_rounds: int         # attach rounds until medoid-reachable


def resolve_finish_backend(backend: str) -> str:
    """Resolve ``"auto"`` (-> the device path); validate the name."""
    if backend not in FINISH_BACKENDS:
        raise ValueError(
            f"unknown finish backend {backend!r}; expected one of "
            f"{FINISH_BACKENDS}")
    return "device" if backend == "auto" else backend


# ---------------------------------------------------------------------------
# Reverse-edge interconnect
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("slots",))
def _reverse_buffer(nbrs: jax.Array, nbr_dists: jax.Array, slots: int):
    """(N, slots) reverse-edge slot buffer via salted scatter-min.

    Every directed edge u->v lands in slot ``hash(u ^ salt) % slots`` of
    v; the nearest source per slot wins (scatter-min on the forward
    distance, then a winner re-scatter of the ids — the two-step keeps
    (id, dist) consistent whatever order XLA applies duplicate updates).
    ``slots`` is oversampled vs the final cap (``_REV_OVERSAMPLE``) so a
    hash collision rarely drops an edge outright; the caller keeps the
    nearest ``rev_cap`` per row — a distance-biased subset, versus the
    host path's arbitrary first-``2R`` truncation.
    """
    n, r = nbrs.shape
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), r)
    dst = nbrs.reshape(-1)
    d = jnp.where(dst >= 0, nbr_dists.reshape(-1), jnp.inf)
    slot = (((src.astype(jnp.uint32) ^ _SALT) * jnp.uint32(2654435761))
            % slots).astype(jnp.int32)
    tgt = jnp.where(dst >= 0, dst, n)
    buf_d = jnp.full((n, slots), jnp.inf, jnp.float32
                     ).at[tgt, slot].min(d, mode="drop")
    win = (d <= buf_d[jnp.minimum(tgt, n - 1), slot]) & (tgt < n)
    buf_i = jnp.full((n, slots), -1, jnp.int32
                     ).at[jnp.where(win, tgt, n), slot].set(src, mode="drop")
    return buf_i, buf_d


def _interconnect_device(data, nbrs, degree, alpha, chunk, rev_cap,
                         merge_backend):
    """Forward ∪ scatter-min reverse -> topk_pool dedup -> re-prune."""
    n, r = nbrs.shape
    node_ids = jnp.arange(n, dtype=jnp.int32)
    nbr_d = rows_sqdist_in_chunks(data, nbrs, chunk)   # the only new dists
    rev_i, rev_d = _reverse_buffer(nbrs, nbr_d, _REV_OVERSAMPLE * rev_cap)
    width = r + rev_cap
    union_parts_i, union_parts_d = [], []
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        # nearest rev_cap of the oversampled buffer — plain top_k, no
        # dedup needed (a row's sources are distinct by construction);
        # forward edges are NEVER truncated (they carry the pruned
        # graph's long-range links), matching the host union's
        # forward ∪ capped-reverse
        negd, pos = jax.lax.top_k(-rev_d[s:e], rev_cap)
        ri = jnp.take_along_axis(rev_i[s:e], pos, axis=1)
        ids = jnp.concatenate([nbrs[s:e], ri], axis=1)
        ds = jnp.concatenate([nbr_d[s:e], -negd], axis=1)
        ids, ds = topk_pool(ids, ds, width, backend=merge_backend)
        union_parts_i.append(ids)
        union_parts_d.append(ds)
    union_i = jnp.concatenate(union_parts_i)
    union_d = jnp.concatenate(union_parts_d)
    out = prune_in_chunks(data, node_ids, union_i, union_d, degree, chunk,
                          alpha)
    return out, width, n * r


def _interconnect_host(data, nbrs, degree, alpha, chunk, rev_cap):
    """The original host path, bit-for-bit: ragged append, first-cap
    truncation, argsort + mark_dups dedup, re-prune."""
    n = nbrs.shape[0]
    node_ids = jnp.arange(n, dtype=jnp.int32)
    nbrs_np = np.asarray(nbrs)
    rev_lists = [[] for _ in range(n)]
    src, dst = np.nonzero(nbrs_np >= 0)
    for p, q in zip(src, nbrs_np[src, dst]):
        rev_lists[q].append(p)
    rev = np.full((n, rev_cap), -1, np.int32)
    for v, lst in enumerate(rev_lists):
        lst = lst[:rev_cap]
        rev[v, : len(lst)] = lst
    union = np.concatenate([nbrs_np, rev], axis=1)
    union_j = jnp.asarray(union)
    union_d = rows_sqdist_in_chunks(data, union_j, chunk)
    order = jnp.argsort(union_d, axis=1)
    union_j = jnp.take_along_axis(union_j, order, axis=1)
    union_d = jnp.take_along_axis(union_d, order, axis=1)
    dup = mark_dups(union_j)
    union_j = jnp.where(dup, -1, union_j)
    union_d = jnp.where(dup, jnp.inf, union_d)
    order = jnp.argsort(union_d, axis=1)
    union_j = jnp.take_along_axis(union_j, order, axis=1)
    union_d = jnp.take_along_axis(union_d, order, axis=1)
    out = prune_in_chunks(data, node_ids, union_j, union_d, degree, chunk,
                          alpha)
    width = union.shape[1]
    return out, width, n * width


def interconnect(data, nbrs, *, degree: int, alpha: float = 1.0,
                 chunk: int = 2048, backend: str = "auto",
                 rev_cap: Optional[int] = None,
                 merge_backend: Optional[str] = None):
    """Reverse-edge interconnect + re-prune (NSG phase 4).

    Returns (pruned (N, degree) neighbors, union width, union distance
    evals). ``rev_cap`` bounds the reverse buffer (default 2 * degree,
    the host path's historical cap — union width is then 3R for both
    backends and the accounting matches the pre-device formula).
    """
    backend = resolve_finish_backend(backend)
    rev_cap = rev_cap if rev_cap is not None else 2 * degree
    if backend == "host":
        return _interconnect_host(data, nbrs, degree, alpha, chunk, rev_cap)
    return _interconnect_device(data, nbrs, degree, alpha, chunk, rev_cap,
                                merge_backend)


# ---------------------------------------------------------------------------
# Reachability
# ---------------------------------------------------------------------------


@jax.jit
def propagate_reach(nbrs: jax.Array, seed: jax.Array) -> jax.Array:
    """Close a (N,) bool seed set under edge-following, to fixpoint.

    Iterative frontier propagation — one boolean scatter over every edge
    whose source is already reached, repeated inside a ``while_loop``
    (early exit the hop after nothing new is reached). O(E) work per hop,
    hops = the seed set's eccentricity — which is why the repair loop
    seeds it incrementally with just-attached nodes instead of re-running
    from the medoid every round.
    """
    n = nbrs.shape[0]

    def body(state):
        reach, _, it = state
        tgt = jnp.where((nbrs >= 0) & reach[:, None], nbrs, n)
        new = reach.at[tgt.reshape(-1)].set(True, mode="drop")
        return new, jnp.any(new != reach), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it <= n)

    # carries typed after the inputs (uniformly varying under shard_map)
    reach, _, _ = jax.lax.while_loop(
        cond, body, (seed, match_vma(jnp.asarray(True), nbrs, seed),
                     match_vma(jnp.asarray(0), nbrs, seed)))
    return reach


def reachable_mask(nbrs: jax.Array, medoid) -> jax.Array:
    """(N,) bool: reachable from the medoid over the directed adjacency.

    The device replacement for the host BFS (``propagate_reach`` seeded
    with the medoid alone).
    """
    n = nbrs.shape[0]
    seed = jnp.zeros((n,), bool).at[jnp.asarray(medoid)].set(True)
    return propagate_reach(nbrs, seed)


# ---------------------------------------------------------------------------
# Batched connectivity repair
# ---------------------------------------------------------------------------


@jax.jit
def _parent_candidates(nbrs, prot, reach, knn_ids, force):
    """Per node: first reachable kNN parent that can accept an edge.

    ``acceptable`` parents are reachable rows with a free slot or at least
    one unprotected (evictable) slot; under ``force`` every reachable row
    accepts (protection is overridden — the host path's pathological
    fallback). Returns (parent (N,), has_parent (N,), acceptable (N,)).
    """
    acceptable = jnp.any(nbrs < 0, axis=1) | jnp.any(~prot, axis=1)
    acceptable = (acceptable | force) & reach
    pk = knn_ids
    ok = (pk >= 0) & acceptable[jnp.maximum(pk, 0)]
    first = jnp.argmax(ok, axis=1)
    has = jnp.any(ok, axis=1)
    rows = jnp.arange(pk.shape[0])
    parent = jnp.where(has, pk[rows, first], -1)
    return parent, has, acceptable


@jax.jit
def _nearest_acceptable(data, norms, acceptable, blk):
    """Exact nearest acceptable parent for a padded block of node ids."""
    safe = jnp.maximum(blk, 0)
    q = data[safe].astype(jnp.float32)
    d = (jnp.sum(q * q, -1, keepdims=True) + norms[None, :]
         - 2.0 * q @ data.astype(jnp.float32).T)
    mask = acceptable[None, :] & (jnp.arange(data.shape[0])[None, :]
                                  != blk[:, None])
    d = jnp.where(mask, d, jnp.inf)
    best = jnp.argmin(d, axis=1).astype(jnp.int32)
    found = jnp.isfinite(jnp.take_along_axis(d, best[:, None], 1)[:, 0])
    return jnp.where(found & (blk >= 0), best, -1)


@jax.jit
def _choose_winners(data, nbrs, prot, reach, parent, force):
    """(N,) bool: nodes that attach this round (one per parent).

    Conflicts resolve by scatter-min on d(node, parent) with a node-id
    tie-break (the two-scatter winner idiom from nn_descent); a winner
    only stands if its parent can place it — a free slot, or an occupied
    slot that is unprotected (or ``force``). Deliberately distance-free
    on the slot side: WHICH slot is evicted needs distances, whether ONE
    exists does not, so the dense per-node pass stays O(N * (R + D)).
    """
    n, r = nbrs.shape
    rows = jnp.arange(n, dtype=jnp.int32)
    i32max = jnp.iinfo(jnp.int32).max
    missing = ~reach
    valid = missing & (parent >= 0)
    safe_p = jnp.maximum(parent, 0)
    pvec = data[safe_p].astype(jnp.float32)
    uvec = data.astype(jnp.float32)
    d_up = jnp.where(valid, jnp.sum((pvec - uvec) ** 2, -1), jnp.inf)
    best_d = jnp.full((n,), jnp.inf, jnp.float32
                      ).at[jnp.where(valid, parent, n)].min(d_up,
                                                            mode="drop")
    cand = valid & (d_up <= best_d[safe_p])
    best_u = jnp.full((n,), i32max, jnp.int32
                      ).at[jnp.where(cand, parent, n)].min(rows, mode="drop")
    win = cand & (best_u[safe_p] == rows)
    prow = nbrs[safe_p]
    can_place = (jnp.any(prow < 0, axis=1)
                 | jnp.any((~prot[safe_p] | force) & (prow >= 0), axis=1))
    return win & can_place


@jax.jit
def _apply_block(data, nbrs, prot, parent, blk, force):
    """Attach one padded block of winning nodes in place.

    The slot rule (first free, else the farthest *unprotected* edge —
    protection overridden only under ``force``) needs the parent row's
    edge distances, so it runs compacted over the winner block, never
    densely over N. Winners hold distinct parents, so in-block scatters
    cannot conflict. The new edge's slot is marked protected — never
    evicted by later rounds. Returns (nbrs, prot, eviction count).
    """
    n, r = nbrs.shape
    ok = blk >= 0
    u = jnp.maximum(blk, 0)
    p = parent[u]
    ok &= p >= 0
    sp = jnp.maximum(p, 0)
    prow = nbrs[sp]                                        # (B, R)
    free = prow < 0
    has_free = jnp.any(free, axis=1)
    first_free = jnp.argmax(free, axis=1)
    pvec = data[sp].astype(jnp.float32)
    dr = jnp.sum((data[jnp.maximum(prow, 0)].astype(jnp.float32)
                  - pvec[:, None, :]) ** 2, -1)
    evictable = ~prot[sp] | force
    dr = jnp.where(evictable & (prow >= 0), dr, -1.0)
    evict_slot = jnp.argmax(dr, axis=1)
    can_evict = jnp.take_along_axis(dr, evict_slot[:, None], 1)[:, 0] >= 0
    slot = jnp.where(has_free, first_free, evict_slot)
    ok &= has_free | can_evict
    tgt = jnp.where(ok, p, n)
    nbrs = nbrs.at[tgt, slot].set(u, mode="drop")
    prot = prot.at[tgt, slot].set(True, mode="drop")
    n_evicted = jnp.sum(ok & ~has_free, dtype=jnp.int32)
    return nbrs, prot, n_evicted


def _padded_blocks(ids: np.ndarray):
    """Yield (block, count) of ``ids`` padded with -1 to ``_FB_BLOCK`` —
    fixed shapes, so the jitted block fns never retrace on the count."""
    for s in range(0, len(ids), _FB_BLOCK):
        blk = ids[s: s + _FB_BLOCK]
        blk_p = np.full((_FB_BLOCK,), -1, np.int32)
        blk_p[: len(blk)] = blk
        yield blk_p, len(blk)


def _repair_round(data, nbrs, prot, reach, parent, force):
    """One attach round: dense winner selection + compacted application.

    Returns (nbrs, prot, placed-node mask, eviction count — evictions are
    the only way previously reachable nodes can become unreachable, so
    the driver only re-verifies reachability from scratch when > 0).
    """
    win = _choose_winners(data, nbrs, prot, reach, parent, force)
    ids = np.nonzero(np.asarray(win))[0].astype(np.int32)
    n_evict = 0
    for blk_p, _ in _padded_blocks(ids):
        nbrs, prot, ne = _apply_block(data, nbrs, prot, parent,
                                      jnp.asarray(blk_p), force)
        n_evict += int(ne)
    return nbrs, prot, win, n_evict


def repair_connectivity_device(data, nbrs, medoid, knn_ids, *,
                               max_rounds: int = 64,
                               return_protected: bool = False):
    """Batched spanning-tree repair: rounds of (reach -> attach-all).

    Per round every unreachable node proposes an edge beneath its first
    reachable kNN parent that can accept (or, lacking one, its exact
    nearest acceptable node — chunked so orphan count never retraces);
    each parent accepts its nearest proposer. Repair edges are protected
    from eviction, so attachments are monotone; chaining across islands
    happens between rounds when reachability is extended. ``force``
    (protection override, the host path's pathological fallback) only
    arms after a round places nothing.

    Reachability is maintained *incrementally*: attaching only adds
    edges, so between rounds the reach set is closed from the
    just-placed nodes (``propagate_reach`` seeded with them) instead of
    re-running the full medoid fixpoint — the expensive full pass runs
    once up front and once more per authoritative exit check, and only
    when an eviction (the one reach-shrinking operation) happened since.
    """
    nbrs = jnp.asarray(nbrs)
    knn_ids = jnp.asarray(knn_ids)
    prot = jnp.zeros(nbrs.shape, bool)
    n = nbrs.shape[0]
    norms = jnp.sum(jnp.asarray(data).astype(jnp.float32) ** 2, axis=-1)
    rounds = 0
    force = False
    reach = reachable_mask(nbrs, medoid)
    exact = True          # no eviction since `reach` was last recomputed
    # while on the ATTACH count: authoritative re-verification iterations
    # are free, so the only exit paths are a verified fixpoint or
    # max_rounds genuine attach rounds (the host path's cap semantics) —
    # never a stale optimistic reach claim
    while rounds < max_rounds:
        missing_np = np.asarray(~reach)
        if not missing_np.any():
            if exact:
                break
            reach = reachable_mask(nbrs, medoid)   # authoritative check
            exact = True
            continue
        parent, has, acceptable = _parent_candidates(
            nbrs, prot, reach, knn_ids, jnp.asarray(force))
        need = missing_np & ~np.asarray(has)
        if need.any():
            fb = np.full((n,), -1, np.int32)
            ids = np.nonzero(need)[0].astype(np.int32)
            for blk_p, cnt in _padded_blocks(ids):
                got = _nearest_acceptable(data, norms, acceptable,
                                          jnp.asarray(blk_p))
                fb[blk_p[:cnt]] = np.asarray(got)[:cnt]
            parent = jnp.where(jnp.asarray(need), jnp.asarray(fb), parent)
        nbrs, prot, placed, n_evict = _repair_round(
            data, nbrs, prot, reach, parent, jnp.asarray(force))
        rounds += 1
        force = not bool(np.asarray(placed).any())  # stalled: override once
        exact = exact and int(n_evict) == 0
        reach = propagate_reach(nbrs, reach | placed)
    if return_protected:
        return nbrs, prot, rounds
    return nbrs, rounds


def ensure_connected_host(nbrs: np.ndarray, data: np.ndarray, medoid: int,
                          knn_ids: np.ndarray) -> Tuple[np.ndarray, int]:
    """BFS from medoid; attach unreachable nodes beneath their nearest
    reachable kNN parent (or the medoid), NSG's spanning-tree repair.
    The original sequential host path, kept as the parity baseline.
    Returns (repaired neighbors, repair rounds)."""
    n, degree = nbrs.shape
    protected = {}       # parent -> repair-edge slots: never evicted, so
    # repairs are monotone and full rows can't ping-pong across rounds
    rounds = 0
    for _ in range(64):  # fixpoint: attaching can unlock whole islands
        seen = np.zeros(n, bool)
        frontier = [medoid]
        seen[medoid] = True
        while frontier:
            nxt = []
            for u in frontier:
                for v in nbrs[u]:
                    if v >= 0 and not seen[v]:
                        seen[v] = True
                        nxt.append(int(v))
            frontier = nxt
        missing = np.nonzero(~seen)[0]
        if missing.size == 0:
            break
        rounds += 1
        for u in missing:
            def try_attach(parent):
                row = nbrs[parent]
                free = np.nonzero(row < 0)[0]
                if free.size:
                    slot = int(free[0])
                else:
                    # evict the farthest *evictable* edge; protected repair
                    # edges stay, else repairs undo each other forever
                    dr = ((data[row] - data[parent]) ** 2).sum(-1)
                    for ss in protected.get(parent, ()):
                        dr[ss] = -1.0
                    slot = int(np.argmax(dr))
                    if dr[slot] < 0:
                        return False        # row is all repair edges
                nbrs[parent, slot] = u
                protected.setdefault(parent, set()).add(slot)
                seen[u] = True  # u reachable; its subtree fixed next round
                return True

            # cheap path first: u's reachable kNNs as parents
            placed = any(try_attach(int(p)) for p in knn_ids[u]
                         if p >= 0 and seen[p])
            if not placed:
                # fallback (only when no kNN parent placed u): nearest
                # reachable nodes by true distance — over the LIVE seen
                # set, so nodes attached earlier this round can chain (a
                # far-out cluster attaches internally instead of every
                # member thrashing one distant parent's full row)
                seen_ids = np.nonzero(seen)[0]
                du = ((data[seen_ids] - data[u]) ** 2).sum(-1)
                near = [int(p) for p in seen_ids[np.argsort(du)[:16]]]
                placed = any(try_attach(p) for p in near)
                if not placed:
                    # every candidate row saturated with protected repairs
                    # (pathological): force-evict from the nearest parent
                    # so connectivity is guaranteed, not best-effort
                    parent = near[0]
                    dr = ((data[nbrs[parent]] - data[parent]) ** 2).sum(-1)
                    slot = int(np.argmax(dr))
                    nbrs[parent, slot] = u
                    protected.setdefault(parent, set()).add(slot)
                    seen[u] = True
    return nbrs, rounds


def repair(data, nbrs, medoid, knn_ids, *, backend: str = "auto"):
    """Connectivity repair (NSG phase 5) -> (jnp neighbors, rounds)."""
    backend = resolve_finish_backend(backend)
    if backend == "host":
        out, rounds = ensure_connected_host(
            np.array(nbrs), np.asarray(data), int(medoid),
            np.asarray(knn_ids))
        return jnp.asarray(out), rounds
    return repair_connectivity_device(data, nbrs, medoid, knn_ids)


# ---------------------------------------------------------------------------
# The full finishing pass
# ---------------------------------------------------------------------------


def finish_nsg(data, nbrs, medoid, knn_ids, *, degree: int,
               alpha: float = 1.0, chunk: int = 2048,
               backend: str = "auto", rev_cap: Optional[int] = None,
               merge_backend: Optional[str] = None):
    """Interconnect + repair: pruned (N, R) adjacency -> servable graph.

    Returns (neighbors (N, degree) jnp, ``FinishStats``). Both stages are
    timed to completion (``block_until_ready``) so the per-stage seconds
    in ``NSGBuildStats`` / BENCH_build.json measure real work.
    """
    resolved = resolve_finish_backend(backend)
    t0 = time.perf_counter()
    out, width, union_evals = interconnect(
        data, nbrs, degree=degree, alpha=alpha, chunk=chunk,
        backend=resolved, rev_cap=rev_cap, merge_backend=merge_backend)
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    out, rounds = repair(data, out, medoid, knn_ids, backend=resolved)
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    return out, FinishStats(
        backend=resolved, union_width=int(width),
        union_dist_evals=int(union_evals),
        interconnect_seconds=t1 - t0, repair_seconds=t2 - t1,
        repair_rounds=int(rounds))
