"""Shard-local graph derivation: reprune + repair with NO host round-trip.

``ShardedIndex.reprune`` used to pull every shard's neighbors back to host
numpy, re-prune there, and re-place the ``(s*m, R)`` table on the mesh —
host RAM, not device FLOPs, capped the derivable N. This module restates
the whole derivation (distance-sorted adjacency -> α-RNG occlusion scan ->
connectivity repair) as ONE fixed-shape jittable program, so it runs
*under ``shard_map``*: each device derives its own shard's serving graph
in place and the result never leaves the mesh.

Two deliberate deviations from the host-orchestrated device repair in
``core/build/finish.py`` (which keeps Python control flow between jitted
rounds and therefore cannot run inside ``shard_map``):

  * the exact nearest-reachable fallback parent (an O(orphans * N)
    scan, host-compacted there) is replaced by the *medoid* as the
    fallback parent — every unreachable node without an acceptable
    reachable kNN parent proposes the navigating node instead. Same
    guarantee (the medoid is reachable by definition), same protected
    -slot monotonicity; attachment locality is slightly worse for the
    rare orphan without reachable kNNs, which recall-level tests cover;
  * rounds are a ``lax.while_loop`` with reachability recomputed from
    the medoid each round (the incremental-reach bookkeeping is host
    logic). The round cap is static; ``force`` (protection override)
    arms after a round that places nothing, exactly like the host path.

The prune stage is bit-identical to ``build.prune.reprune`` (same sorted
adjacency, same occlusion scan) — tier-1 asserted; only the repair tail
may differ, and only for nodes the reprune disconnected.

Everything here also serves the chunked host-offload tier
(``core.distributed.StreamedShardedIndex``): the same jitted program runs
per-shard on a single device while shards stream through HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.build.finish import _choose_winners, propagate_reach
from repro.core.build.prune import alpha_prune, pairwise_rows_sqdist
from repro.core.distances import match_vma

# Row-block size for the lax.map-streamed passes below: bounds every f32
# temp at (BLK, R[, D]) whatever the shard size is.
_BLK = 1024


def _blocked(fn, n_rows: int, *arrays, blk: int = _BLK):
    """Run ``fn`` over fixed-size row blocks via ``lax.map`` (jit-safe).

    Pads each array's leading dim up to a block multiple (ids with -1,
    floats with 0) and slices the result back — the in-jit analogue of
    the host chunk loops in ``build.prune``, so per-structure f32 temps
    stay (blk, ...)-sized inside a single fused program.
    """
    n_pad = -(-n_rows // blk) * blk
    padded = []
    for a in arrays:
        pad = [(0, n_pad - n_rows)] + [(0, 0)] * (a.ndim - 1)
        cval = -1 if jnp.issubdtype(a.dtype, jnp.integer) else 0
        padded.append(jnp.pad(a, pad, constant_values=cval).reshape(
            (n_pad // blk, blk) + a.shape[1:]))
    out = jax.lax.map(fn, tuple(padded))
    return out.reshape((n_pad,) + out.shape[2:])[:n_rows]


def _edge_dists(data: jax.Array, nbrs: jax.Array, blk: int = _BLK):
    """(N, R) d(i, nbrs[i]) — blocked, +inf at -1 padding."""
    rows = jnp.arange(nbrs.shape[0], dtype=jnp.int32)

    def f(args):
        rb, ib = args
        return pairwise_rows_sqdist(data[jnp.maximum(rb, 0)], data, ib)

    return _blocked(f, nbrs.shape[0], rows, nbrs, blk=blk)


def _reprune_blocked(data, nbrs, degree: int, alpha, blk: int = _BLK):
    """Streamed sort + α-scan: bit-identical to ``build.prune.reprune``."""
    rows = jnp.arange(nbrs.shape[0], dtype=jnp.int32)

    def f(args):
        rb, ib = args
        d = pairwise_rows_sqdist(data[jnp.maximum(rb, 0)], data, ib)
        order = jnp.argsort(d, axis=1, stable=True)
        ci = jnp.take_along_axis(ib, order, axis=1)
        cd = jnp.take_along_axis(d, order, axis=1)
        return alpha_prune(data, rb, ci, cd, degree, alpha)

    return _blocked(f, nbrs.shape[0], rows, nbrs, blk=blk)


def _apply_dense(data, nbrs, prot, parent, win, force, blk: int = _BLK):
    """Attach every winning node beneath its parent, dense over N.

    The slot rule matches ``finish._apply_block`` (first free slot, else
    the farthest unprotected edge; protection overridden only under
    ``force``); winners hold distinct parents (scatter-min winner
    selection), so the dense scatters cannot conflict. Returns
    (nbrs, prot, placed mask).
    """
    n, r = nbrs.shape
    u = jnp.arange(n, dtype=jnp.int32)
    ok = win & (parent >= 0)
    sp = jnp.maximum(jnp.where(ok, parent, 0), 0)
    prow = nbrs[sp]
    free = prow < 0
    has_free = jnp.any(free, axis=1)
    first_free = jnp.argmax(free, axis=1)
    dr = _edge_dists(data, nbrs, blk=blk)[sp]
    evictable = ~prot[sp] | force
    dr = jnp.where(evictable & (prow >= 0), dr, -1.0)
    evict_slot = jnp.argmax(dr, axis=1)
    can_evict = jnp.take_along_axis(dr, evict_slot[:, None], 1)[:, 0] >= 0
    slot = jnp.where(has_free, first_free, evict_slot)
    ok &= has_free | can_evict
    tgt = jnp.where(ok, parent, n)
    nbrs = nbrs.at[tgt, slot].set(u, mode="drop")
    prot = prot.at[tgt, slot].set(True, mode="drop")
    return nbrs, prot, ok


@functools.partial(jax.jit, static_argnames=("max_rounds", "blk"))
def repair_local(data: jax.Array, nbrs: jax.Array, knn_ids: jax.Array,
                 medoid, valid: Optional[jax.Array] = None, *,
                 max_rounds: int = 16, blk: int = _BLK):
    """Fully-jittable connectivity repair (the shard_map-safe tail).

    Rounds of (reach from medoid -> all unreachable valid nodes propose a
    parent -> one attach per parent): parents are the first *acceptable*
    reachable kNN parent (free or evictable slot — always acceptable
    under ``force``), falling back to the medoid. Repair edges are
    protected from later eviction, so attachment is monotone; ``force``
    arms after a round that places nothing. ``valid`` masks padded rows
    (they are never missing, never parents). Returns (nbrs, rounds).
    """
    n, r = nbrs.shape
    if valid is None:
        valid = jnp.ones((n,), bool)
    medoid = jnp.asarray(medoid, jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)
    seed = jnp.zeros((n,), bool).at[medoid].set(True)
    # carries typed after the inputs (uniformly varying under shard_map)
    refs = (data, nbrs, knn_ids, medoid, valid)
    prot0 = match_vma(jnp.zeros((n, r), bool), *refs)
    reach0 = propagate_reach(nbrs, seed) & valid

    def cond(st):
        nbrs, prot, reach, force, rounds = st
        return (rounds < max_rounds) & jnp.any(valid & ~reach)

    def body(st):
        nbrs, prot, reach, force, rounds = st
        acceptable = reach & (jnp.any(nbrs < 0, axis=1)
                              | jnp.any(~prot, axis=1) | force)
        pk_ok = (knn_ids >= 0) & acceptable[jnp.maximum(knn_ids, 0)]
        first = jnp.argmax(pk_ok, axis=1)
        has = jnp.any(pk_ok, axis=1)
        parent = jnp.where(has, knn_ids[rows, first], medoid)
        parent = jnp.where(valid & ~reach & (parent != rows), parent, -1)
        # reach | ~valid: padded rows are never "missing" to the winner
        # selection (shared with finish.py's host-driven repair)
        win = _choose_winners(data, nbrs, prot, reach | ~valid, parent,
                              force)
        nbrs, prot, placed = _apply_dense(data, nbrs, prot, parent, win,
                                          force, blk=blk)
        reach = propagate_reach(nbrs, seed) & valid
        force = ~jnp.any(placed)
        return nbrs, prot, reach, force, rounds + 1

    nbrs, _, _, _, rounds = jax.lax.while_loop(
        cond, body, (nbrs, prot0, reach0,
                     match_vma(jnp.asarray(False), *refs),
                     match_vma(jnp.asarray(0), *refs)))
    return nbrs, rounds


@functools.partial(jax.jit,
                   static_argnames=("degree", "max_rounds", "repair",
                                    "blk"))
def derive_local(base: jax.Array, neighbors: jax.Array,
                 knn_ids: jax.Array, medoid,
                 valid: Optional[jax.Array] = None, *,
                 alpha=1.0, degree: Optional[int] = None,
                 max_rounds: int = 16, repair: bool = True,
                 blk: int = _BLK) -> jax.Array:
    """One shard's (alpha, degree) serving graph from its cached
    max-degree adjacency — sort, α-scan, repair, all in one jit.

    ``alpha`` is a traced scalar (one compile serves the whole alpha
    grid); ``degree`` is static (it is the output shape). Designed to be
    the body of a ``shard_map``: no host control flow, f32 temps bounded
    at (blk, R). With ``repair=False`` returns the pure prune stage —
    bit-identical to ``build.prune.reprune`` (tier-1 asserted).
    """
    n, rmax = neighbors.shape
    degree = rmax if degree is None else min(degree, rmax)
    base = base.astype(jnp.float32)
    nbrs = _reprune_blocked(base, neighbors, degree,
                            jnp.asarray(alpha, jnp.float32), blk=blk)
    if not repair:
        return nbrs
    nbrs, _ = repair_local(base, nbrs, knn_ids, medoid, valid,
                           max_rounds=max_rounds, blk=blk)
    return nbrs
