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

Two batch layouts:
  * ``vmap``: per-query program, lifted over the batch by `jax.vmap` (the
    original formulation — one (R, D) gather per query per hop).
  * ``batched``: batch-major — all Q queries step together, so each hop is
    ONE (Q, R) id block fed to a single gather+distance call. Converged
    queries are masked out per hop (`lax.select` on the lane state), which
    reproduces `vmap(while_loop)` semantics bit-for-bit: both layouts
    return identical ids and distances.

Two hop backends (batched layout only):
  * ``staged``: gather + distance (``kernels/gather_dist`` /
    ``kernels/lut_dist``) and pool merge as separate device ops — the
    parity baseline, and the default off-TPU.
  * ``fused``: one ``kernels/beam_hop`` launch per hop — the scalar-prefetch
    kernel gathers the graph row, streams the R candidate rows, scores them
    in-register and merges into the resident pool, so the (Q, R) candidate
    block never round-trips through HBM. Bit-exact with the staged path
    when the staged path runs the kernel-family arithmetic
    (``gather_backend="jnp"|"pallas"``); the dot-formula default gather
    (`_default_gather_dist`) is a different f32 reduction order.

Straggler control (batched layout):
  * **Adaptive early exit** (``patience`` / ``eps``): the stock termination
    rule runs a lane until its whole pool is visited. Long before that, the
    top-k prefix — the only part of the pool the caller sees — has usually
    stopped moving. With ``patience=p`` a lane also terminates once ``p``
    consecutive hops fail to improve any of its top-k prefix distances by
    more than ``eps`` (eps=0: any strict improvement counts as progress).
    ``patience=None`` disables the rule and reproduces the stock semantics
    bit-for-bit; ``patience >= max_iters`` provably never fires.
  * **Active-query compaction** (``beam_search_compacted``): even a
    terminated lane keeps riding its batch's (Q, R) hop blocks until the
    LAST lane converges — the ``wasted_hops`` counter prices exactly that.
    The compacted driver runs hop slices of ``compact_every`` hops, gathers
    the surviving lanes into the smallest power-of-two bucket that holds
    them (``serve/batching.pow2_buckets`` — a pre-warmed shape set, so
    compaction never retraces) and scatters finished results back to their
    original slots. Lanes never interact, so results are bit-identical to
    the uncompacted path; only ``wasted_hops`` shrinks.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

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
    working (each one still pays a (Q, R) row through the hop block).
    Always 0 under the vmap layout (per-query programs exit individually);
    under the batched layout it is what adaptive termination shrinks and
    compaction eliminates, so it differs — by design — between the plain
    and compacted drivers while hops/gathered/dup_gathered stay identical.
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


def _select_frontier(pool_i, pool_d, pool_v):
    """Pick the closest unvisited pool entry and mark it visited.

    Axis-generic over the trailing (ef) axis, so the vmap layout ((ef,)
    arrays), the batched layout ((Q, ef) arrays) and the fused hop all
    share the one copy. Returns (pool_v, node, active): ``node`` is 0 when
    the lane has converged (``active`` False) — the caller masks.
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


def _expand(state, query, db, neighbors, gather_dist):
    pool_i, pool_d, pool_v, n_hops, n_gath, n_dup = state
    pool_v, node, active = _select_frontier(pool_i, pool_d, pool_v)
    nbr = neighbors[node]                         # (R,)
    valid = (nbr >= 0) & active
    safe = jnp.where(valid, nbr, 0)
    nd = gather_dist(query, db, safe)             # (R,) squared L2
    nd = jnp.where(valid, nd, jnp.inf)
    pool_i, pool_d, pool_v, dup = merge_one(
        pool_i, pool_d, pool_v, jnp.where(valid, safe, -1), nd)
    return (pool_i, pool_d, pool_v, n_hops + active.astype(jnp.int32),
            n_gath + jnp.sum(valid, dtype=jnp.int32), n_dup + dup)


def _expand_batch(state, queries, db, neighbors, gather_dist_b):
    """Batch-major `_expand`: one (Q, R) gather + distance block per hop."""
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
    """None -> the Pallas kernel on TPU, the fused-jnp reference elsewhere.

    Returning ``None`` (off-TPU default) selects the vmapped
    `_default_gather_dist`, whose lowering is identical to the vmap layout's
    — that is what makes the two layouts agree exactly.

    The ``REPRO_GATHER_BACKEND`` env var ("pallas" | "jnp") overrides the
    default resolution only (an explicit ``backend`` argument wins). Note
    the resolver runs at trace time inside jitted callers: an env change
    after the first compile does not invalidate their caches.
    """
    if backend is None:
        backend = os.environ.get("REPRO_GATHER_BACKEND") or None
    if backend is None:
        return "pallas" if jax.default_backend() == "tpu" else None
    if backend not in ("pallas", "jnp"):
        raise ValueError(f"unknown gather backend {backend!r} "
                         f"(expected 'pallas' | 'jnp')")
    return backend


def resolve_hop_backend(backend: Optional[str] = None) -> str:
    """None/"auto" -> the fused kernel on TPU, the staged path elsewhere.

    Staged stays the off-TPU default so the CPU layout-parity contract
    (dot-formula gather == vmap layout bit-for-bit) is undisturbed; on TPU
    both defaults resolve to the same kernel-family arithmetic, so flipping
    to fused changes launches per hop, not served bits. Overridable via the
    ``REPRO_HOP_BACKEND`` env var (same trace-time caveat as
    ``resolve_gather_backend``).
    """
    if backend in (None, "auto"):
        backend = os.environ.get("REPRO_HOP_BACKEND") or None
    if backend in (None, "auto"):
        return "fused" if jax.default_backend() == "tpu" else "staged"
    if backend not in ("staged", "fused"):
        raise ValueError(f"unknown hop backend {backend!r} "
                         f"(expected 'staged' | 'fused' | 'auto')")
    return backend


@functools.partial(
    jax.jit,
    static_argnames=("ef", "k", "max_iters", "mode", "gather_dist",
                     "layout", "gather_backend", "dist_backend",
                     "hop_backend", "patience", "eps", "with_stats"))
def beam_search(queries: jax.Array, db: jax.Array, neighbors: jax.Array,
                entry_ids: jax.Array, *, ef: int, k: int,
                max_iters: int = 0, mode: str = "while",
                gather_dist: Optional[Callable] = None,
                layout: str = "vmap",
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

    ``layout="vmap"`` lifts a per-query program over the batch;
    ``layout="batched"`` steps all queries together so each hop issues one
    (Q, R) expansion — `gather_backend` then picks the expansion kernel
    ("pallas" | "jnp" via kernels/gather_dist; None = pallas on TPU, the
    layout-parity jnp path elsewhere). A custom ``gather_dist`` callable
    takes (D,),(N,D),(R,) under "vmap" and (Q,D),(N,D),(Q,R) under
    "batched".

    ``dist_backend="pq"|"int8"`` traverses over quantized codes instead of
    ``db``: pass the codec's ``codes`` (N, M) uint8 and per-query ``lut``
    (Q, M, C) f32 and every hop becomes one ``kernels/lut_dist`` call —
    R rows of M bytes instead of R rows of D*4. Only the batched layout
    supports it (the hot path); returned distances are then approximate
    ADC values, which the caller reranks exactly (``Index.search``).

    ``hop_backend="staged"|"fused"`` (batched layout only) picks whether a
    hop runs as separate gather/distance/merge ops or as one
    ``kernels/beam_hop`` launch; None/"auto" resolves fused on TPU, staged
    elsewhere. Under "fused", ``gather_backend`` still picks the kernel
    flavour ("pallas" = the real fused kernel, "jnp" = its bit-exact ref).

    ``patience``/``eps`` (batched layout only) enable adaptive early
    termination: a lane also stops after ``patience`` consecutive hops in
    which no top-k prefix distance improved by more than ``eps``.
    ``patience=None`` (default) keeps the stock full-pool-convergence rule
    bit-for-bit.
    """
    max_iters = max_iters or 4 * ef
    if eps < 0.0:
        raise ValueError(f"eps must be >= 0, got {eps}")
    if patience is not None and patience < 1:
        raise ValueError(
            f"patience must be >= 1 (or None to disable), got {patience}")
    if dist_backend != "f32" and layout != "batched":
        raise ValueError(
            f"dist_backend={dist_backend!r} requires layout='batched' "
            f"(the quantized hot path), got layout={layout!r}")
    if patience is not None and layout != "batched":
        raise ValueError(
            "patience requires layout='batched' (adaptive termination "
            "exists to cut batch straggler cost; the vmap layout has none)")
    if layout == "batched":
        return _beam_search_batched(
            queries, db, neighbors, entry_ids, ef=ef, k=k,
            max_iters=max_iters, mode=mode, gather_dist=gather_dist,
            gather_backend=gather_backend, dist_backend=dist_backend,
            codes=codes, lut=lut, hop_backend=hop_backend,
            patience=patience, eps=eps, with_stats=with_stats)
    if layout != "vmap":
        raise ValueError(f"bad layout {layout!r}")
    if hop_backend == "fused":
        raise ValueError(
            "hop_backend='fused' requires layout='batched' (the fused "
            "kernel is batch-major); the vmap layout is always staged")
    if gather_dist is None:
        gather_dist = _default_gather_dist

    def one(query, entry):
        d0 = gather_dist(query, db, entry[None])[0]
        # derive constant initializers from the inputs so the loop carry is
        # uniformly device-varying under shard_map (JAX 0.8 VMA typing).
        pool_i = match_vma(jnp.full((ef,), -1, jnp.int32), query, db,
                           neighbors, entry).at[0].set(entry)
        pool_d = jnp.full((ef,), jnp.inf, jnp.float32).at[0].set(d0)
        pool_d = match_vma(pool_d, query, db, neighbors, entry)
        pool_v = match_vma(jnp.zeros((ef,), bool), query, db, neighbors,
                           entry)
        zero = match_vma(jnp.int32(0), query, db, neighbors, entry)
        state = (pool_i, pool_d, pool_v, zero, zero, zero)

        body = lambda s: _expand(s, query, db, neighbors, gather_dist)
        if mode == "while":
            def cond(s):
                i, d, v, hops = s[0], s[1], s[2], s[3]
                return jnp.any((~v) & (i >= 0)) & (hops < max_iters)
            state = jax.lax.while_loop(cond, body, state)
        elif mode == "fori":
            state = jax.lax.fori_loop(0, max_iters, lambda _, s: body(s),
                                      state)
        else:
            raise ValueError(f"bad mode {mode!r}")
        pool_i, pool_d, _, hops, gath, dup = state
        return pool_d[:k], pool_i[:k], hops, gath, dup

    d, i, hops, gath, dup = jax.vmap(one)(queries, entry_ids)
    if with_stats:
        # per-query programs exit individually: no batch-ride overhead
        return d, i, BeamStats(hops, gath, dup, jnp.zeros_like(hops))
    return d, i, hops


def _batched_hop_setup(queries, db, neighbors, *, gather_dist,
                       gather_backend, dist_backend, codes, lut,
                       hop_backend):
    """Resolve the hop backend + distance callable and build the per-hop
    body over the 6-tuple core state.

    Shared by the jitted batched path and the compaction drivers
    (``_compact_seed`` / ``_hop_slice``) so every entry point traces the
    same arithmetic — that sharing is what makes compaction bit-identical.
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
            # of kernels/gather_dist, not the dot-formula default: seed the
            # pool from the same kernel family so the entry distances carry
            # the bits the hops will reproduce
            gd = functools.partial(_kernel_gather_dist,
                                   backend=backend or "jnp")
        elif backend is None:
            # vmap of the per-query fn lowers to the same batched dot_general
            # as the "vmap" layout traces — exact cross-layout agreement.
            gd = jax.vmap(_default_gather_dist, in_axes=(0, None, 0))
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


def _seed_batched(queries, db, neighbors, entry_ids, ef, gd):
    """Entry-seeded 8-tuple loop state for the batched layout.

    (pool_i, pool_d, pool_v, hops, gathered, dup_gathered, wasted, stale).
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


def _lane_live(state, *, max_iters, patience):
    """Per-lane "still working" mask over the 8-tuple state."""
    pool_i, pool_v, hops = state[0], state[2], state[3]
    live = jnp.any((~pool_v) & (pool_i >= 0), axis=1) & (hops < max_iters)
    if patience is not None:
        live = live & (state[7] < patience)
    return live


def _run_hops(state, body, *, k, max_iters, mode, patience, eps,
              max_steps=None):
    """Advance the 8-tuple batched loop state to convergence (or by
    ``max_steps`` hop iterations — the compaction slice).

    One hop: freeze-select on the pre-hop live mask (exactly the stock
    guarded while-loop step, so ``patience=None`` is bit-identical to the
    historical 6-tuple loop), plus the two straggler counters: ``stale``
    (consecutive no-progress hops, adaptive mode only) and ``wasted``
    (iterations ridden while not live — updated OUTSIDE the freeze-select,
    since the frozen lanes are precisely the ones accruing it).

    In fori mode the guarded step is bit-identical to the historical
    unguarded body for ``patience=None``: a converged lane's expansion is
    already a natural no-op (inactive frontier, all-invalid merge), and the
    hop budget can't exceed the trip count mid-loop. Adaptive termination
    needs the guard (a stale lane still has unvisited pool entries).
    """
    adaptive = patience is not None
    live_of = functools.partial(_lane_live, max_iters=max_iters,
                                patience=patience)

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
        # mirror vmap(while_loop) batching: run while ANY lane wants to,
        # freeze lanes whose own cond is false.
        if max_steps is None:
            return jax.lax.while_loop(
                lambda s: jnp.any(live_of(s)), hop, state)

        def cond(c):
            return (c[0] < max_steps) & jnp.any(live_of(c[1]))
        _, state = jax.lax.while_loop(
            cond, lambda c: (c[0] + 1, hop(c[1])),
            (jnp.zeros((), jnp.int32), state))
        return state
    if mode == "fori":
        n = max_iters if max_steps is None else max_steps
        return jax.lax.fori_loop(0, n, lambda _, s: hop(s), state)
    raise ValueError(f"bad mode {mode!r}")


def _beam_search_batched(queries, db, neighbors, entry_ids, *, ef, k,
                         max_iters, mode, gather_dist, gather_backend,
                         dist_backend="f32", codes=None, lut=None,
                         hop_backend=None, patience=None, eps=0.0,
                         with_stats=False):
    db, codes, lut = _kernel_tables(db, codes, lut, gather_dist=gather_dist,
                                    gather_backend=gather_backend,
                                    dist_backend=dist_backend)
    gd, body = _batched_hop_setup(
        queries, db, neighbors, gather_dist=gather_dist,
        gather_backend=gather_backend, dist_backend=dist_backend,
        codes=codes, lut=lut, hop_backend=hop_backend)
    state = _seed_batched(queries, db, neighbors, entry_ids, ef, gd)
    state = _run_hops(state, body, k=k, max_iters=max_iters, mode=mode,
                      patience=patience, eps=eps)
    pool_i, pool_d, _, hops, gath, dup, wasted, _ = state
    if with_stats:
        return (pool_d[:, :k], pool_i[:, :k],
                BeamStats(hops, gath, dup, wasted))
    return pool_d[:, :k], pool_i[:, :k], hops


def _default_gather_dist(query: jax.Array, db: jax.Array,
                         ids: jax.Array) -> jax.Array:
    return _sqdist_rows(query, db[ids])


@functools.partial(
    jax.jit,
    static_argnames=("ef", "gather_dist", "gather_backend", "dist_backend",
                     "hop_backend"))
def _compact_seed(queries, db, neighbors, entry_ids, *, ef,
                  gather_dist=None, gather_backend=None,
                  dist_backend="f32", codes=None, lut=None,
                  hop_backend=None):
    """Jitted pool seeding for the compacted driver (bucket-stable shapes)."""
    gd, _ = _batched_hop_setup(
        queries, db, neighbors, gather_dist=gather_dist,
        gather_backend=gather_backend, dist_backend=dist_backend,
        codes=codes, lut=lut, hop_backend=hop_backend)
    return _seed_batched(queries, db, neighbors, entry_ids, ef, gd)


@functools.partial(
    jax.jit,
    static_argnames=("k", "max_iters", "gather_dist", "gather_backend",
                     "dist_backend", "hop_backend", "patience", "eps",
                     "max_steps"))
def _hop_slice(state, queries, db, neighbors, *, k, max_iters,
               gather_dist=None, gather_backend=None, dist_backend="f32",
               codes=None, lut=None, hop_backend=None, patience=None,
               eps=0.0, max_steps=1):
    """Advance the batched loop state by one compaction slice.

    Runs up to ``max_steps`` guarded while-mode hops (exits early when every
    lane in the batch is done) and returns ``(state, live)`` where ``live``
    is the per-lane continuation mask the host compacts on. Every static
    argument is a hashable primitive, so the jit cache holds exactly one
    entry per (bucket shape × knob setting) — compaction re-dispatches into
    warm entries instead of retracing.
    """
    _, body = _batched_hop_setup(
        queries, db, neighbors, gather_dist=gather_dist,
        gather_backend=gather_backend, dist_backend=dist_backend,
        codes=codes, lut=lut, hop_backend=hop_backend)
    state = _run_hops(state, body, k=k, max_iters=max_iters, mode="while",
                      patience=patience, eps=eps, max_steps=max_steps)
    live = _lane_live(state, max_iters=max_iters, patience=patience)
    return state, live


def _mask_lanes_dead(state, start):
    """Make lanes ``start:`` inert: empty pool -> never live, results inf/-1."""
    pool_i, pool_d = state[0], state[1]
    return ((pool_i.at[start:].set(-1), pool_d.at[start:].set(jnp.inf))
            + state[2:])


def beam_search_compacted(queries: jax.Array, db: jax.Array,
                          neighbors: jax.Array, entry_ids: jax.Array, *,
                          ef: int, k: int, compact_every: int,
                          max_iters: int = 0, mode: str = "while",
                          gather_dist: Optional[Callable] = None,
                          gather_backend: Optional[str] = None,
                          dist_backend: str = "f32",
                          codes: Optional[jax.Array] = None,
                          lut: Optional[jax.Array] = None,
                          hop_backend: Optional[str] = None,
                          patience: Optional[int] = None,
                          eps: float = 0.0,
                          with_stats: bool = False,
                          buckets: Optional[Sequence[int]] = None,
                          shape_log: Optional[list] = None):
    """``beam_search(layout="batched")`` with active-query compaction.

    Host-side driver: runs ``compact_every``-hop jitted slices, and between
    slices gathers the still-live lanes into the smallest power-of-two
    bucket that holds them (``serve/batching.pow2_buckets`` — the same
    pre-warmable shape set the serve path uses, so shrinking never
    retraces), scattering each finished lane's results back to its original
    slot as it drops out. Batch cost then tracks the *distribution* of
    per-query hop counts instead of the max.

    Lanes never interact (vmapped gathers, per-row merges), so ids, dists,
    hops, gathered and dup_gathered are bit-identical to the uncompacted
    path; ``wasted_hops`` is what shrinks — a lane stops riding at its
    first post-termination slice boundary. ``shape_log``, when given, has
    each slice's dispatched batch size appended (tests assert it is
    bucket-snapped and non-increasing).

    Only while-mode semantics exist here (fori's fixed trip count is the
    straggler cost this driver removes), and stats are flushed per lane, so
    ``with_stats`` shapes match ``beam_search``'s exactly.
    """
    if mode != "while":
        raise ValueError(
            f"compaction requires mode='while' (mode={mode!r}): a fixed "
            f"fori trip count is exactly the straggler cost it removes")
    if compact_every < 1:
        raise ValueError(f"compact_every must be >= 1, got {compact_every}")
    if eps < 0.0:
        raise ValueError(f"eps must be >= 0, got {eps}")
    if patience is not None and patience < 1:
        raise ValueError(
            f"patience must be >= 1 (or None to disable), got {patience}")
    from repro.serve.batching import bucket_for, pow2_buckets

    nq = queries.shape[0]
    max_iters = max_iters or 4 * ef
    buckets = tuple(sorted(pow2_buckets(nq) if buckets is None
                           else set(int(b) for b in buckets)))
    quantized = dist_backend != "f32"

    def pad_rows(a, b):
        n = a.shape[0]
        if n == b:
            return a
        return jnp.concatenate(
            [a, jnp.broadcast_to(a[:1], (b - n,) + a.shape[1:])], axis=0)

    slice_kw = dict(gather_dist=gather_dist, gather_backend=gather_backend,
                    dist_backend=dist_backend, hop_backend=hop_backend)
    # padded once for every slice of this search, not once per slice
    db, codes, lut = _kernel_tables(db, codes, lut, gather_dist=gather_dist,
                                    gather_backend=gather_backend,
                                    dist_backend=dist_backend)

    b0 = bucket_for(nq, buckets)
    q_cur = pad_rows(jnp.asarray(queries), b0)
    lut_cur = pad_rows(lut, b0) if quantized else None
    state = _compact_seed(q_cur, db, neighbors,
                          pad_rows(jnp.asarray(entry_ids), b0), ef=ef,
                          codes=codes, lut=lut_cur, **slice_kw)
    state = _mask_lanes_dead(state, nq)
    orig = np.arange(b0, dtype=np.int64)
    orig[nq:] = -1

    out_d = np.full((nq, k), np.inf, np.float32)
    out_i = np.full((nq, k), -1, np.int32)
    out_stats = np.zeros((4, nq), np.int32)   # hops, gathered, dup, wasted

    def flush(done_rows):
        pool_i, pool_d = np.asarray(state[0]), np.asarray(state[1])
        counters = [np.asarray(c) for c in state[3:7]]
        dst = orig[done_rows]
        out_d[dst] = pool_d[done_rows, :k]
        out_i[dst] = pool_i[done_rows, :k]
        for buf, c in zip(out_stats, counters):
            buf[dst] = c[done_rows]
        orig[done_rows] = -1

    # hops strictly increases for every live lane, so the slice loop is
    # bounded; the +1 covers the all-dead exit slice.
    for _ in range(-(-max_iters // compact_every) + 1):
        state, live = _hop_slice(state, q_cur, db, neighbors, k=k,
                                 max_iters=max_iters, codes=codes,
                                 lut=lut_cur, patience=patience, eps=eps,
                                 max_steps=compact_every, **slice_kw)
        if shape_log is not None:
            shape_log.append(int(q_cur.shape[0]))
        live_np = np.asarray(live)
        done = np.nonzero((~live_np) & (orig >= 0))[0]
        if done.size:
            flush(done)
        survivors = np.nonzero(live_np)[0]
        if survivors.size == 0:
            break
        nb = bucket_for(survivors.size, buckets)
        if nb < q_cur.shape[0]:
            idx = np.full(nb, survivors[0], np.int64)
            idx[:survivors.size] = survivors
            take = jnp.asarray(idx)
            state = tuple(a[take] for a in state)
            state = _mask_lanes_dead(state, survivors.size)
            q_cur = q_cur[take]
            lut_cur = lut_cur[take] if quantized else None
            orig = np.concatenate(
                [orig[survivors],
                 np.full(nb - survivors.size, -1, np.int64)])

    d, i = jnp.asarray(out_d), jnp.asarray(out_i)
    hops = jnp.asarray(out_stats[0])
    if with_stats:
        return d, i, BeamStats(hops, jnp.asarray(out_stats[1]),
                               jnp.asarray(out_stats[2]),
                               jnp.asarray(out_stats[3]))
    return d, i, hops
