"""Batched NN-Descent (Dong et al., WWW'11) as a device-resident program.

The exact substrate (``core/knn_graph.py``) is O(N^2 D) — fine at 100k
vectors, hopeless at the SISAP 10M/30M scale. NN-Descent converges to a
high-recall kNN graph in near-linear distance evaluations by repeatedly
joining each node's neighborhood against itself ("a neighbor of a neighbor
is likely a neighbor").

This implementation restates the classic asynchronous heap algorithm as
fixed-shape jitted rounds over one device-resident ``(N, K)`` neighbor
table (ids + squared dists + the classic new/old "fresh" flag):

  0. *init*: ``init_passes`` random-projection block joins (EFANNA-style)
     — sort along a random direction, join contiguous ``init_bsize``
     blocks with one MXU tile each — seed the table with projection-local
     neighbors for N * bsize evaluations per pass.
  1. *sample*: per row, up to ``s_fwd`` fresh and ``s_fwd`` old neighbor
     positions (fresh-first priority sort), plus ``s_rev``-slot reverse
     samples — every directed edge u->v scatters its flat edge index into
     a random slot of v's fresh/old bucket (collisions drop, the standard
     fixed-shape stand-in for ragged reverse lists).
  2. *local join* (classic new x (new ∪ old)): one (B, Mr, Mc) distance
     tile per row block — rows are {self} ∪ fresh samples, columns add the
     old samples (batched MXU matmuls over gathered vectors + precomputed
     norms). Every valid pair (a, b) is a *proposal*: push b into a's
     neighbor list and a into b's.
  3. *update*: proposals fold into a fixed (N, U) slot buffer keyed by
     target node via per-slot scatter-min (slot = per-round-salted hash of
     the proposed id, so bucket collisions never systematically exclude a
     neighbor), then a fixed-shape sort/dedup merge folds buffer + the
     tile's own row into each row's top-K. No distance is ever recomputed
     — proposals carry d(a, b) from the join tile.
  4. rounds early-exit when the fraction of changed table entries drops
     below ``delta``.

NN-Descent converges to local optima when the table is narrow, so small
requested k runs with a wider internal table (``k_build``) truncated on
return.

Distance-evaluation counts are tracked exactly (valid tile lanes, not
padding) so benchmarks compare backends on work, not just wall-clock.

Note: the proposal scatter writes ids and dists through two scatters with
identical duplicate indices; XLA applies duplicate scatter updates in
order on CPU/TPU, keeping the pair consistent (GPU would need the packed
variant).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distances import EXACT
from repro.kernels.topk_merge import resolve_merge_backend, topk_merge


def _host_sum(per_block_counts) -> int:
    """Sum per-block int32 eval counts in Python ints (no int32 wrap)."""
    return int(np.sum(np.asarray(per_block_counts), dtype=np.int64))


class BuildStats(NamedTuple):
    """Work accounting for one kNN-graph build."""
    backend: str
    n: int
    k: int
    distance_evals: int    # pairwise distance evaluations issued
    rounds: int            # refinement rounds actually run (exact: 1)
    update_rate: float     # last round's fraction of changed table entries


def _merge(cur_i, cur_d, cur_f, cand_i, cand_d, k, backend):
    """Merge (B, K) current rows with (B, M) candidates -> new top-k rows.

    Dedup keeps the *existing* copy of an id (fresh=False) so re-proposed
    neighbors are not resampled as new next round. The primitive lives in
    ``kernels/topk_merge``: a stable-argsort jnp path (the CPU default,
    bit-identical to the historical inline merge) and a Pallas bitonic
    network (the TPU default — XLA sorts don't lower inside kernels).
    """
    return topk_merge(cur_i, cur_d, cur_f, cand_i, cand_d, k,
                      backend=backend)


def _pad_rows(x, rows, fill):
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)), constant_values=fill)


def _fold_merge(ids, dists, fresh, cand_i, cand_d, block, backend):
    """Blockwise ``_merge`` of per-row candidates (with known dists)."""
    n, k = ids.shape
    nb = -(-n // block)
    u = cand_i.shape[1]

    def mstep(args):
        ci, cd, cf, bi, bd = args
        return _merge(ci, cd, cf, bi, bd, k, backend)

    out_i, out_d, out_f = jax.lax.map(mstep, (
        _pad_rows(ids, nb * block, -1).reshape(nb, block, k),
        _pad_rows(dists, nb * block, jnp.inf).reshape(nb, block, k),
        _pad_rows(fresh, nb * block, False).reshape(nb, block, k),
        _pad_rows(cand_i, nb * block, -1).reshape(nb, block, u),
        _pad_rows(cand_d, nb * block, jnp.inf).reshape(nb, block, u)))
    return (out_i.reshape(nb * block, k)[:n],
            out_d.reshape(nb * block, k)[:n],
            out_f.reshape(nb * block, k)[:n])


@functools.partial(jax.jit, static_argnames=("bsize", "block", "backend"))
def _rp_block_join(key, data, norms, ids, dists, fresh, bsize, block,
                   backend):
    """One random-projection block join (the EFANNA-style init pass).

    Sort all points along a random 1-D projection, cut the order into
    contiguous ``bsize`` blocks, and join each block against itself with
    one (bsize, bsize) MXU tile — projection locality makes same-block
    points likely true neighbors, so a couple of passes build a far better
    starting table than random draws, for N * bsize distance evaluations
    per pass.
    """
    n, k = ids.shape
    nb2 = -(-n // bsize)
    pad = nb2 * bsize - n
    proj = data @ jax.random.normal(key, (data.shape[1],))
    order = jnp.argsort(proj).astype(jnp.int32)            # sorted node ids
    order_p = jnp.concatenate(
        [order, jnp.full((pad,), -1, jnp.int32)]).reshape(nb2, bsize)

    def one(_, g):
        safe = jnp.maximum(g, 0)
        vecs = data[safe].astype(jnp.float32)
        nn = norms[safe]
        t = jnp.maximum(nn[:, None] + nn[None, :]
                        - 2.0 * jnp.matmul(vecs, vecs.T, precision=EXACT),
                        0.0)
        valid = ((g[:, None] >= 0) & (g[None, :] >= 0)
                 & (g[:, None] != g[None, :]))
        ci = jnp.where(valid, jnp.broadcast_to(g[None, :], t.shape), -1)
        cd = jnp.where(valid, t, jnp.inf)
        # per-block count (summed host-side: int32 would wrap at 10M+ N)
        return None, (ci, cd, jnp.sum(valid, dtype=jnp.int32))

    _, (ci, cd, n_eval) = jax.lax.scan(one, None, order_p)
    # un-permute: sorted position s belongs to node order_p[s]
    tgt = jnp.where(order_p.reshape(-1) >= 0, order_p.reshape(-1), n)
    cand_i = jnp.full((n, bsize), -1, jnp.int32
                      ).at[tgt].set(ci.reshape(-1, bsize), mode="drop")
    cand_d = jnp.full((n, bsize), jnp.inf, jnp.float32
                      ).at[tgt].set(cd.reshape(-1, bsize), mode="drop")
    out = _fold_merge(ids, dists, fresh, cand_i, cand_d, block, backend)
    return out + (n_eval,)


@jax.jit
def _seed_dists_chunk(data, norms, rows, init_chunk):
    """(b, I) init ids for ``rows`` -> (ids, dists, n_valid), distances in
    ``data``'s space."""
    valid = ((init_chunk >= 0) & (init_chunk < data.shape[0])
             & (init_chunk != rows[:, None]))
    safe = jnp.maximum(jnp.where(valid, init_chunk, 0), 0)
    vecs = data[safe].astype(jnp.float32)
    q = data[rows].astype(jnp.float32)
    d = (norms[rows][:, None] + norms[safe]
         - 2.0 * jnp.einsum("bkd,bd->bk", vecs, q, precision=EXACT))
    return (jnp.where(valid, init_chunk, -1),
            jnp.where(valid, jnp.maximum(d, 0.0), jnp.inf),
            jnp.sum(valid, dtype=jnp.int32))


def _seed_from_init(data, norms, ids, dists, fresh, init_ids, block,
                    backend):
    """Fold a caller-supplied (N, I) id table into the empty table.

    Distances are (re)computed in *this* data's space — the init table may
    come from another metric space entirely (the antihub-subset reuse path
    feeds raw-space neighbors into the PCA-projected build) — and each
    valid non-self entry counts as one distance evaluation. The gather +
    distance pass runs in ``block``-row chunks like every other distance
    pass in the build stack, so the (N, I, D) gathered tensor never
    materializes at once.
    """
    n = data.shape[0]
    ci_parts, cd_parts, counts = [], [], []
    for s in range(0, n, block):
        e = min(s + block, n)
        ci, cd, c = _seed_dists_chunk(
            data, norms, jnp.arange(s, e, dtype=jnp.int32), init_ids[s:e])
        ci_parts.append(ci)
        cd_parts.append(cd)
        counts.append(c)
    out = _fold_merge(ids, dists, fresh, jnp.concatenate(ci_parts),
                      jnp.concatenate(cd_parts), block, backend)
    return out + (_host_sum(jnp.stack(counts)),)


@functools.partial(
    jax.jit,
    static_argnames=("s_fwd", "s_rev", "u_slots", "block", "backend"))
def _round(key, data, norms, ids, dists, fresh, s_fwd, s_rev, u_slots,
           block, backend):
    """One sample -> local-join -> update round. Returns new state + #changed."""
    n, k = ids.shape
    kf, ko, kr, kh = jax.random.split(key, 4)
    rows = jnp.arange(n, dtype=jnp.int32)

    # -- sample fresh-first and old-first neighbor positions per row -------
    def take(prio_key, prefer_fresh, count):
        pri = jax.random.uniform(prio_key, (n, k))
        pri = pri + jnp.where(fresh == prefer_fresh, 0.0, 1.0)
        pri = jnp.where(ids >= 0, pri, 2.0)                  # padding last
        pos = jnp.argsort(pri, axis=1)[:, :count]
        return pos, jnp.take_along_axis(ids, pos, axis=1)

    pos_new, samp_new = take(kf, True, s_fwd)
    _, samp_old = take(ko, False, s_fwd)

    # -- reverse sample: edge u->v scatters its flat index into one of two
    # buckets of v (fresh edges / old edges), the fixed-shape stand-in for
    # ragged reverse lists (collisions drop; rounds re-draw slots) ---------
    v = ids.reshape(-1)
    ef = fresh.reshape(-1)
    kr1, kr2 = jax.random.split(kr)

    def rev_sample(sel, slots, skey):
        slot = jax.random.randint(skey, (n * k,), 0, slots)
        ptr = jnp.full((n, slots), -1, jnp.int32)
        ptr = ptr.at[jnp.where(sel & (v >= 0), v, n), slot].set(
            jnp.arange(n * k, dtype=jnp.int32), mode="drop")
        return jnp.where(ptr >= 0, ptr // k, -1)             # source node u

    rev_new = rev_sample(ef, s_rev, kr1)
    rev_old = rev_sample(~ef, s_rev, kr2)
    fresh = fresh.at[rows[:, None], pos_new].set(False)      # sampled -> old

    # join sets (classic NND: new x (new ∪ old)): tile rows are the node
    # itself + its fresh samples, tile cols add the old samples
    jrows = jnp.concatenate([rows[:, None], samp_new, rev_new], axis=1)
    jcols = jnp.concatenate([jrows, samp_old, rev_old], axis=1)
    mr, mc = jrows.shape[1], jcols.shape[1]

    # -- local join: one (B, Mr, Mc) distance tile per row block. Row 0
    # (the node itself) feeds its own list directly; every other pair
    # (a, b) proposes b into a's list AND a into b's, folded into a global
    # (N, U) buffer. Per-slot scatter-min keeps the *best* proposal per
    # hash bucket (slot = salted-hash(id) dedups repeated proposals; the
    # salt is re-drawn per round so bucket collisions never systematically
    # exclude a neighbor); the block-local winner re-gather keeps
    # (id, dist) consistent without a second distance pass.
    nb = -(-n // block)
    rows_p = _pad_rows(jrows, nb * block, -1).reshape(nb, block, mr)
    cols_p = _pad_rows(jcols, nb * block, -1).reshape(nb, block, mc)
    salt = jax.random.randint(kh, (), 0, jnp.iinfo(jnp.int32).max)

    def hash_slot(val):
        h = (val.astype(jnp.uint32) ^ salt.astype(jnp.uint32))
        return ((h * jnp.uint32(2654435761)) % u_slots).astype(jnp.int32)

    def step(carry, inp):
        buf_v, buf_d = carry
        ra, cb = inp                                         # (B, Mr), (B, Mc)
        va = data[jnp.maximum(ra, 0)].astype(jnp.float32)    # (B, Mr, D)
        vb = data[jnp.maximum(cb, 0)].astype(jnp.float32)    # (B, Mc, D)
        t = (norms[jnp.maximum(ra, 0)][:, :, None]
             + norms[jnp.maximum(cb, 0)][:, None, :]
             - 2.0 * jnp.einsum("bmd,bnd->bmn", va, vb, precision=EXACT))
        t = jnp.maximum(t, 0.0)
        a_id = jnp.broadcast_to(ra[:, :, None], t.shape)
        b_id = jnp.broadcast_to(cb[:, None, :], t.shape)
        valid = (a_id >= 0) & (b_id >= 0) & (a_id != b_id)
        # per-block eval count (summed host-side: int32 wraps at 10M+ N)
        n_eval = jnp.sum(valid, dtype=jnp.int32)
        # (a) direct: row 0 of the tile is d(self, c) for every column
        dir_i = jnp.where(valid[:, 0, 1:], cb[:, 1:], -1)
        dir_d = jnp.where(valid[:, 0, 1:], t[:, 0, 1:], jnp.inf)
        # (b) cross proposals, both directions, minus the direct row
        valid = valid.at[:, 0, :].set(False)
        dd = jnp.where(valid, t, jnp.inf).reshape(-1)
        dd = jnp.concatenate([dd, dd])
        targ = jnp.concatenate([jnp.where(valid, a_id, n).reshape(-1),
                                jnp.where(valid, b_id, n).reshape(-1)])
        val = jnp.concatenate([b_id.reshape(-1), a_id.reshape(-1)])
        sl = hash_slot(val)
        blk_d = jnp.full((n, u_slots), jnp.inf, jnp.float32)
        blk_d = blk_d.at[targ, sl].min(dd, mode="drop")
        win = (dd <= blk_d[jnp.minimum(targ, n - 1), sl]) & (targ < n)
        blk_v = jnp.full((n, u_slots), -1, jnp.int32)
        blk_v = blk_v.at[jnp.where(win, targ, n), sl].set(val, mode="drop")
        better = blk_d < buf_d
        buf_v = jnp.where(better, blk_v, buf_v)
        buf_d = jnp.where(better, blk_d, buf_d)
        return (buf_v, buf_d), (dir_i, dir_d, n_eval)

    buf_v = jnp.full((n, u_slots), -1, jnp.int32)
    buf_d = jnp.full((n, u_slots), jnp.inf, jnp.float32)
    (buf_v, buf_d), (dir_i, dir_d, n_eval) = jax.lax.scan(
        step, (buf_v, buf_d), (rows_p, cols_p))
    dir_i = dir_i.reshape(nb * block, mc - 1)[:n]
    dir_d = dir_d.reshape(nb * block, mc - 1)[:n]

    # -- fold direct + proposal candidates into the table (no new dists) ---
    cat_i = jnp.concatenate([dir_i, buf_v], axis=1)
    cat_d = jnp.concatenate([dir_d, buf_d], axis=1)
    out_i, out_d, out_f = _fold_merge(ids, dists, fresh, cat_i, cat_d, block,
                                      backend)
    changed = jnp.sum((out_i != ids) & (out_i >= 0))
    return out_i, out_d, out_f, changed, n_eval


def nn_descent(data: jax.Array, k: int, *, key: Optional[jax.Array] = None,
               rounds: int = 15, delta: float = 0.001, s_fwd: int = 5,
               s_rev: Optional[int] = None, u_slots: Optional[int] = None,
               k_build: Optional[int] = None, init_passes: int = 4,
               init_bsize: int = 32, block: int = 2048,
               init_ids: Optional[jax.Array] = None,
               merge_backend: Optional[str] = None,
               with_stats: bool = False):
    """Approximate (N, k) kNN graph; same contract as ``knn_graph``.

    Returns (dists (N, k) f32 ascending, ids (N, k) i32, self excluded,
    -1/inf padded in the degenerate k >= N case) — plus a ``BuildStats``
    when ``with_stats`` is set.

    ``k_build`` is the internal table width: NN-Descent converges to local
    optima when the table is narrow (the classic small-K failure mode), so
    small requested k runs with a wider table that is truncated on return.

    ``init_ids`` (N, I) seeds the table from a caller-supplied neighbor
    id table (-1 padded; distances recomputed here, one eval per valid
    entry). This is the "filter + patch" reuse path: a kNN table built on
    a superset (or in another projection of) this data warm-starts the
    refinement, so a couple of ``rounds`` replace a from-scratch build.

    ``merge_backend`` picks the dedup-top-k merge primitive
    (``kernels/topk_merge``): None = bitonic Pallas kernel on TPU, the
    stable-argsort jnp path elsewhere.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    merge_backend = resolve_merge_backend(merge_backend)
    n = data.shape[0]
    k_build = k_build if k_build is not None else max(k, min(2 * k, 20))
    kk = min(max(k_build, k), n - 1) if n > 1 else 1
    k_out = min(k, n - 1) if n > 1 else 1
    block = min(block, max(n, 1))
    s_fwd = min(s_fwd, kk)
    s_rev = s_rev if s_rev is not None else s_fwd
    u_slots = u_slots if u_slots is not None else max(2 * kk, 16)

    data = data.astype(jnp.float32)
    norms = jnp.sum(data * data, axis=-1)

    # init: a few random-projection block joins instead of random draws —
    # each pass costs N * init_bsize evaluations and seeds the table with
    # projection-local (likely true) neighbors, saving several refinement
    # rounds (the EFANNA-style initialization).
    ids = jnp.full((n, kk), -1, jnp.int32)
    dists = jnp.full((n, kk), jnp.inf, jnp.float32)
    fresh = jnp.zeros((n, kk), bool)
    evals = 0
    if init_ids is not None:
        ids, dists, fresh, n_eval = _seed_from_init(
            data, norms, ids, dists, fresh,
            jnp.asarray(init_ids, jnp.int32), block, merge_backend)
        evals += _host_sum(n_eval)
    bsize = min(init_bsize, n)
    for _ in range(init_passes):
        key, sub = jax.random.split(key)
        ids, dists, fresh, n_eval = _rp_block_join(
            sub, data, norms, ids, dists, fresh, bsize, block,
            merge_backend)
        evals += _host_sum(n_eval) + n    # tile evals + the projection pass
    rate = 1.0
    r = 0
    for r in range(1, rounds + 1):
        key, sub = jax.random.split(key)
        ids, dists, fresh, changed, n_eval = _round(
            sub, data, norms, ids, dists, fresh, s_fwd, s_rev, u_slots,
            block, merge_backend)
        evals += _host_sum(n_eval)
        rate = float(changed) / float(n * kk)
        if rate <= delta:
            break

    ids = ids[:, :k_out]
    dists = dists[:, :k_out]
    if k_out < k:                 # degenerate tiny-N case: pad out to k
        padw = k - k_out
        dists = jnp.pad(dists, ((0, 0), (0, padw)), constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, padw)), constant_values=-1)
    if with_stats:
        stats = BuildStats(backend="nndescent", n=n, k=k,
                           distance_evals=int(evals), rounds=r,
                           update_rate=rate)
        return dists, ids, stats
    return dists, ids

