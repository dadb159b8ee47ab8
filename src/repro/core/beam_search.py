"""Fixed-width beam (best-first) graph traversal — TPU-native NSG search.

The CPU algorithm (Faiss NSG / HNSW) keeps a dynamic priority queue and a
visited hash set and computes one scalar L2 per popped neighbor. None of that
maps to a TPU. This module adapts the *algorithm's invariant* — "repeatedly
expand the closest unvisited candidate; keep the ef best seen" — to fixed
shapes:

  * the candidate pool is a distance-sorted (ef,) triple (ids, dists, visited)
    updated by a masked merge-sort each expansion;
  * one expansion gathers all R neighbors of the best unvisited node and
    evaluates their distances in a single (R, D) block (the Pallas
    `gather_dist` kernel on TPU; fused gather+matmul here);
  * the visited set is approximated by pool membership + per-entry flags.
    A node evicted from the pool can be re-expanded; the iteration budget
    bounds that extra work (standard fixed-shape ANN trick — recall is
    unaffected, only worst-case work).

Two loop modes:
  * ``while``: `lax.while_loop`, exits when the pool converges (CPU/latency).
  * ``fori``:  fixed `max_iters` trip count — deterministic FLOPs, used by
    the dry-run so `cost_analysis()` is meaningful, and maps to TPU best.

One batch layout: batch-major — all Q queries step together, so each hop is
ONE (Q, R) id block fed to a single gather+distance call. A converged lane
is frozen per hop (`lax.select` on the lane state) while its batch-mates
work on; lanes never interact, so a query's answer in a batch is its answer
alone (the per-query semantics of ``vmap(while_loop)``).

Two hop backends:
  * ``staged``: gather + distance (``kernels/gather_dist`` /
    ``kernels/lut_dist``) and pool merge as separate device ops — the
    parity baseline, and the default off-TPU.
  * ``fused``: one ``kernels/beam_hop`` launch per hop — the scalar-prefetch
    kernel gathers the graph row, streams the R candidate rows, scores them
    in-register and merges into the resident pool, so the (Q, R) candidate
    block never round-trips through HBM. Bit-exact with the staged path
    when the staged path runs the kernel-family arithmetic
    (``gather_backend="jnp"|"pallas"``); the dot formula
    (``dot_gather_dist``) is a different f32 reduction order.

Adaptive early exit (``patience`` / ``eps``): the stock termination rule
runs a lane until its whole pool is visited. Long before that, the top-k
prefix — the only part of the pool the caller sees — has usually stopped
moving. With ``patience=p`` a lane also terminates once ``p`` consecutive
hops fail to improve any of its top-k prefix distances by more than ``eps``
(eps=0: any strict improvement counts as progress). ``patience=None``
disables the rule and reproduces the stock semantics bit-for-bit;
``patience >= max_iters`` provably never fires.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.distances import EXACT, match_vma
from repro.kernels.beam_hop import beam_hop as _kernel_beam_hop
from repro.kernels.beam_hop import merge_one
from repro.kernels.gather_dist import gather_dist as _kernel_gather_dist
from repro.kernels.lut_dist import lut_dist as _kernel_lut_dist
from repro.kernels.row_gather import centroid_major, pad_table


class BeamStats(NamedTuple):
    """Per-query work accounting of one beam_search call.

    ``hops``: expansions taken; ``gathered``: neighbor rows whose distance
    was evaluated; ``dup_gathered``: of those, rows that were already
    pool-resident (work the approximate visited set failed to skip).
    Fused and staged hop backends compute these independently — their
    equality asserts parity on work done, not just results.

    ``wasted_hops``: batch-ride overhead — loop iterations a lane sat
    through after its own termination because batch-mates were still
    working (each one still pays a (Q, R) row through the hop block). It is
    what adaptive termination shrinks.
    """
    hops: jax.Array
    gathered: jax.Array
    dup_gathered: jax.Array
    wasted_hops: jax.Array


def _sqdist_rows(query: jax.Array, rows: jax.Array) -> jax.Array:
    """(D,), (R, D) -> (R,) squared L2, f32 accumulation via matmul."""
    q = query.astype(jnp.float32)
    r = rows.astype(jnp.float32)
    return jnp.maximum(
        jnp.sum(q * q) + jnp.sum(r * r, axis=-1)
        - 2.0 * jnp.matmul(r, q, precision=EXACT), 0.0)


def _default_gather_dist(query: jax.Array, db: jax.Array,
                         ids: jax.Array) -> jax.Array:
    return _sqdist_rows(query, db[ids])


# The dot formula |q|^2 + |x|^2 - 2 q.x, lifted over the batch: the staged
# hop's default off-TPU. Passed as ``gather_dist=`` it pins that arithmetic
# on every platform (a custom callable keeps the hop staged and the table
# unpadded).
dot_gather_dist = jax.vmap(_default_gather_dist, in_axes=(0, None, 0))


def _select_frontier(pool_i, pool_d, pool_v):
    """Pick each lane's closest unvisited pool entry and mark it visited.

    (Q, ef) arrays; shared by the staged and the fused hop. Returns
    (pool_v, node, active): ``node`` is 0 when the lane has converged
    (``active`` False) — the caller masks.
    """
    unvisited = (~pool_v) & (pool_i >= 0)
    masked = jnp.where(unvisited, pool_d, jnp.inf)
    slot = jnp.argmin(masked, axis=-1)
    active = jnp.take_along_axis(unvisited, slot[..., None], -1)[..., 0]
    # unconditional mark: a no-op when inactive (the slot is already True
    # or the whole lane re-selects the same converged state)
    pool_v = pool_v | (jnp.arange(pool_v.shape[-1]) == slot[..., None])
    node = jnp.where(
        active, jnp.take_along_axis(pool_i, slot[..., None], -1)[..., 0], 0)
    return pool_v, node, active


def _expand_batch(state, queries, db, neighbors, gather_dist_b):
    """Staged hop: one (Q, R) gather + distance block, then the merge."""
    pool_i, pool_d, pool_v, n_hops, n_gath, n_dup = state
    pool_v, node, active = _select_frontier(pool_i, pool_d, pool_v)
    nbr = neighbors[node]                         # (Q, R)
    valid = (nbr >= 0) & active[:, None]
    safe = jnp.where(valid, nbr, 0)
    nd = gather_dist_b(queries, db, safe)         # (Q, R) — ONE call per hop
    nd = jnp.where(valid, nd, jnp.inf)
    pool_i, pool_d, pool_v, dup = jax.vmap(merge_one)(
        pool_i, pool_d, pool_v, jnp.where(valid, safe, -1), nd)
    return (pool_i, pool_d, pool_v, n_hops + active.astype(jnp.int32),
            n_gath + jnp.sum(valid, axis=1, dtype=jnp.int32), n_dup + dup)


def _expand_fused(state, q_or_lut, table, neighbors, *, dist_backend,
                  backend):
    """One ``kernels/beam_hop`` launch: gather+distance+merge fused."""
    pool_i, pool_d, pool_v, n_hops, n_gath, n_dup = state
    pool_v, node, active = _select_frontier(pool_i, pool_d, pool_v)
    sel = jnp.where(active, node, -1)
    pool_i, pool_d, pool_v, stats = _kernel_beam_hop(
        sel, neighbors, pool_i, pool_d, pool_v, q_or_lut, table,
        dist_backend=dist_backend, backend=backend)
    return (pool_i, pool_d, pool_v, n_hops + active.astype(jnp.int32),
            n_gath + stats[:, 0], n_dup + stats[:, 1])


def resolve_gather_backend(backend: Optional[str] = None) -> Optional[str]:
    """None -> the Pallas kernel on TPU, the dot formula elsewhere.

    Returning ``None`` (off-TPU default) selects ``dot_gather_dist``.
    """
    if backend is None:
        return "pallas" if jax.default_backend() == "tpu" else None
    if backend not in ("pallas", "jnp"):
        raise ValueError(f"unknown gather backend {backend!r} "
                         f"(expected 'pallas' | 'jnp')")
    return backend


def resolve_hop_backend(backend: Optional[str] = None) -> str:
    """None/"auto" -> the fused kernel on TPU, the staged path elsewhere.

    On TPU both defaults resolve to the same kernel-family arithmetic, so
    fused changes launches per hop, not served bits; off-TPU the staged
    hop keeps the dot formula as the default arithmetic.
    """
    if backend in (None, "auto"):
        return "fused" if jax.default_backend() == "tpu" else "staged"
    if backend not in ("staged", "fused"):
        raise ValueError(f"unknown hop backend {backend!r} "
                         f"(expected 'staged' | 'fused' | 'auto')")
    return backend


@functools.partial(
    jax.jit,
    static_argnames=("ef", "k", "max_iters", "mode", "gather_dist",
                     "gather_backend", "dist_backend", "hop_backend",
                     "patience", "eps", "with_stats"))
def beam_search(queries: jax.Array, db: jax.Array, neighbors: jax.Array,
                entry_ids: jax.Array, *, ef: int, k: int,
                max_iters: int = 0, mode: str = "while",
                gather_dist: Optional[Callable] = None,
                gather_backend: Optional[str] = None,
                dist_backend: str = "f32",
                codes: Optional[jax.Array] = None,
                lut: Optional[jax.Array] = None,
                hop_backend: Optional[str] = None,
                patience: Optional[int] = None,
                eps: float = 0.0,
                with_stats: bool = False):
    """Batched graph search.

    queries: (Q, D); db: (N, D); neighbors: (N, R) int32 (-1 padded);
    entry_ids: (Q,) int32 per-query entry points (paper's tuned EPs).
    Returns (dists (Q, k) f32 ascending, ids (Q, k) i32, hops (Q,) i32);
    with ``with_stats=True`` the third element is a full ``BeamStats``.

    All queries step together, so each hop issues one (Q, R) expansion.
    ``gather_backend`` picks the expansion kernel ("pallas" | "jnp" via
    kernels/gather_dist; None = pallas on TPU, ``dot_gather_dist``
    elsewhere). A custom ``gather_dist`` callable takes
    (Q, D), (N, D), (Q, R) and returns (Q, R) squared distances; it keeps
    the hop staged.

    ``dist_backend="pq"|"int8"`` traverses over quantized codes instead of
    ``db``: pass the codec's ``codes`` (N, M) uint8 and per-query ``lut``
    (Q, M, C) f32 and every hop becomes one ``kernels/lut_dist`` call —
    R rows of M bytes instead of R rows of D*4. Returned distances are then
    approximate ADC values, which the caller reranks exactly
    (``Index.search``).

    ``hop_backend="staged"|"fused"`` picks whether a hop runs as separate
    gather/distance/merge ops or as one ``kernels/beam_hop`` launch;
    None/"auto" resolves fused on TPU, staged elsewhere. Under "fused",
    ``gather_backend`` still picks the kernel flavour ("pallas" = the real
    fused kernel, "jnp" = its bit-exact ref).

    ``patience``/``eps`` enable adaptive early termination: a lane also
    stops after ``patience`` consecutive hops in which no top-k prefix
    distance improved by more than ``eps``. ``patience=None`` (default)
    keeps the stock full-pool-convergence rule bit-for-bit.
    """
    max_iters = max_iters or 4 * ef
    if eps < 0.0:
        raise ValueError(f"eps must be >= 0, got {eps}")
    if patience is not None and patience < 1:
        raise ValueError(
            f"patience must be >= 1 (or None to disable), got {patience}")
    db, codes, lut = _kernel_tables(db, codes, lut, gather_dist=gather_dist,
                                    gather_backend=gather_backend,
                                    dist_backend=dist_backend)
    gd, body = _hop_setup(
        queries, db, neighbors, gather_dist=gather_dist,
        gather_backend=gather_backend, dist_backend=dist_backend,
        codes=codes, lut=lut, hop_backend=hop_backend)
    state = _seed(queries, db, neighbors, entry_ids, ef, gd)
    state = _run_hops(state, body, k=k, max_iters=max_iters, mode=mode,
                      patience=patience, eps=eps)
    pool_i, pool_d, _, hops, gath, dup, wasted, _ = state
    if with_stats:
        return (pool_d[:, :k], pool_i[:, :k],
                BeamStats(hops, gath, dup, wasted))
    return pool_d[:, :k], pool_i[:, :k], hops


def _hop_setup(queries, db, neighbors, *, gather_dist, gather_backend,
               dist_backend, codes, lut, hop_backend):
    """Resolve the hop backend + distance callable and build the per-hop
    body over the 6-tuple core state.

    Returns ``(gd, body)``; ``gd`` also seeds the pool's entry distances.
    Under a quantized ``dist_backend`` the ``queries`` argument is only a
    placeholder for ``gd``'s signature (the LUT carries the per-query
    operand). ``db``/``codes``/``lut`` arrive through ``_kernel_tables``.
    """
    hop = resolve_hop_backend(hop_backend)
    if gather_dist is not None and hop == "fused":
        if hop_backend in (None, "auto"):
            hop = "staged"    # custom distance callables are staged-only
        else:
            raise ValueError(
                "hop_backend='fused' cannot honor a custom gather_dist "
                "callable (distances are computed in-kernel)")
    if dist_backend != "f32":
        backend = resolve_gather_backend(gather_backend) or "jnp"
        gd = lambda q, db_, ids: _kernel_lut_dist(lut, codes, ids,
                                                  backend=backend)
    elif gather_dist is not None:
        gd = gather_dist
    else:
        backend = resolve_gather_backend(gather_backend)
        if hop == "fused":
            # the fused hop's in-kernel arithmetic is the diff-square form
            # of kernels/gather_dist, not the dot formula: seed the pool
            # from the same kernel family so the entry distances carry the
            # bits the hops will reproduce
            gd = functools.partial(_kernel_gather_dist,
                                   backend=backend or "jnp")
        elif backend is None:
            gd = dot_gather_dist
        else:
            gd = functools.partial(_kernel_gather_dist, backend=backend)

    if hop == "fused":
        kb = resolve_gather_backend(gather_backend) or "jnp"
        q_or_lut = queries if dist_backend == "f32" else lut
        table = db if dist_backend == "f32" else codes
        body = lambda s: _expand_fused(s, q_or_lut, table, neighbors,
                                       dist_backend=dist_backend,
                                       backend=kb)
    else:
        body = lambda s: _expand_batch(s, queries, db, neighbors, gd)
    return gd, body


def _kernel_tables(db, codes, lut, *, gather_dist, gather_backend,
                   dist_backend):
    """The row table and ADC table the hops read, in the Pallas kernels'
    layouts.

    The kernels DMA whole (8, 128) tiles (``row_gather.pad_table``) and
    take the ADC table centroid-major (``row_gather.centroid_major``).
    Made by the kernels themselves, inside the hop loop, either would be
    copied on every hop (XLA sinks the pad into the loop body), so each
    search makes them here, once, before its first hop: a no-op for an
    aligned f32 table or off the Pallas path. A quantized search scores
    with ``kernels/lut_dist`` whatever ``gather_dist`` is.
    """
    if dist_backend != "f32" and (codes is None or lut is None):
        raise ValueError(
            f"dist_backend={dist_backend!r} needs codes and lut "
            f"(encode the db with a core.quant codec first)")
    pallas = resolve_gather_backend(gather_backend) == "pallas"
    if dist_backend != "f32":
        if pallas:
            codes, lut = pad_table(codes), centroid_major(lut)
    elif pallas and gather_dist is None:
        db = pad_table(db)
    return db, codes, lut


def _seed(queries, db, neighbors, entry_ids, ef, gd):
    """Entry-seeded 8-tuple loop state.

    (pool_i, pool_d, pool_v, hops, gathered, dup_gathered, wasted, stale).
    Constant initializers derive from the inputs so the loop carry is
    uniformly device-varying under ``shard_map`` (JAX VMA typing).
    """
    nq = queries.shape[0]
    d0 = gd(queries, db, entry_ids[:, None])[:, 0]
    pool_i = match_vma(jnp.full((nq, ef), -1, jnp.int32), queries, db,
                       neighbors, entry_ids).at[:, 0].set(entry_ids)
    pool_d = jnp.full((nq, ef), jnp.inf, jnp.float32).at[:, 0].set(d0)
    pool_d = match_vma(pool_d, queries, db, neighbors, entry_ids)
    pool_v = match_vma(jnp.zeros((nq, ef), bool), queries, db, neighbors,
                       entry_ids)
    zeros = match_vma(jnp.zeros((nq,), jnp.int32), queries, db, neighbors,
                      entry_ids)
    return (pool_i, pool_d, pool_v, zeros, zeros, zeros, zeros, zeros)


def _run_hops(state, body, *, k, max_iters, mode, patience, eps):
    """Advance the 8-tuple loop state to convergence.

    One hop: freeze-select on the pre-hop live mask (exactly the guarded
    per-lane while-loop step, so ``patience=None`` is the stock rule
    bit-for-bit), plus the two straggler counters: ``stale`` (consecutive
    no-progress hops, adaptive mode only) and ``wasted`` (iterations
    ridden while not live — updated OUTSIDE the freeze-select, since the
    frozen lanes are precisely the ones accruing it).

    In fori mode the guard changes nothing for ``patience=None``: a
    converged lane's expansion is already a natural no-op (inactive
    frontier, all-invalid merge), and the hop budget can't exceed the trip
    count mid-loop. Adaptive termination needs the guard (a stale lane
    still has unvisited pool entries).
    """
    adaptive = patience is not None

    def live_of(s):
        pool_i, pool_v, hops = s[0], s[2], s[3]
        live = (jnp.any((~pool_v) & (pool_i >= 0), axis=1)
                & (hops < max_iters))
        if adaptive:
            live = live & (s[7] < patience)
        return live

    def hop(s):
        keep = live_of(s)
        new_core = body(s[:6])
        if adaptive:
            progress = jnp.any(s[1][:, :k] - new_core[1][:, :k] > eps,
                               axis=1)
            stale = jnp.where(progress, jnp.zeros_like(s[7]), s[7] + 1)
        else:
            stale = s[7]
        new = new_core + (s[6], stale)

        def sel(a, b):
            pred = keep.reshape(keep.shape + (1,) * (a.ndim - 1))
            return jnp.where(pred, a, b)
        merged = jax.tree_util.tree_map(sel, new, s)
        wasted = s[6] + (~keep).astype(jnp.int32)
        return merged[:6] + (wasted,) + merged[7:]

    if mode == "while":
        # run while ANY lane wants to, freeze lanes whose own cond is false
        return jax.lax.while_loop(lambda s: jnp.any(live_of(s)), hop, state)
    if mode == "fori":
        return jax.lax.fori_loop(0, max_iters, lambda _, s: hop(s), state)
    raise ValueError(f"bad mode {mode!r}")
