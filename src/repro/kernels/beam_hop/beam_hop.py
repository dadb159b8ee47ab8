"""Fused beam-hop Pallas TPU kernel: gather -> distance -> pool merge.

One beam hop used to be three device round trips — gather the (Q, R)
neighbor ids, score them (``kernels/gather_dist`` or ``kernels/lut_dist``),
then merge into the (Q, ef) pool — with the candidate id and distance
blocks spilled to HBM between stages. Here the (Q, R) graph rows of the
selected nodes are one XLA gather (R ids per query), and everything after
it is one launch: each grid step takes ``TB`` queries, streams their R
candidate rows (f32 vectors or uint8 codes, picked by a static
``dist_backend``) HBM->VMEM, scores them in VMEM, and merges them into the
resident pool with a bitonic dedup-merge — the (Q, R) distance block never
touches HBM.

Only live slots are fetched (``row_gather.fetch_live_rows``): a dead lane
(sel < 0) and a neighbour row's -1 padding give -1 ids, and a slot with a
-1 id costs one scalar compare, no DMA, wait or extract. Its row scratch
keeps stale bytes, which the ``cand >= 0`` mask hides from the merge. The
quantized branch takes the ADC table centroid-major, (Q, C, M)
(``row_gather.centroid_major``, made once per search), and scores a
query's R code rows as one block, by a select per centroid; a query with
no live slot skips its scoring, and the same mask hides its stale scores.

Bit-exactness with ``ref.py`` (and therefore with the staged path) is by
construction:

  * f32 distances use the diff-square form of ``kernels/gather_dist``;
    PQ/int8 use ``kernels/lut_dist``'s per-centroid select + left-to-right
    accumulation over M (the scores are the same ``row_gather`` code);
  * a candidate is a duplicate when any pool lane holds its id, found by
    rotating the (pool | candidates) id row past itself;
  * the merge sorts lanes by the lexicographic (distance, input position)
    key, which reproduces the reference's single *stable* argsort exactly —
    including +inf padding ties — via the strict-comparator bitonic network
    shared with ``kernels/topk_merge``.

Grid: (ceil(Q / TB),).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitonic import bitonic_by, network_width
from repro.kernels.row_gather import (
    TB, compiler_params, fetch_live_rows, l2_scores, lut_scores,
    lut_scratch, pad_block_rows, pad_table, row_scratch, slot_scratch,
)


def _stable_gt(self_t, part_t):
    """Strict (dist, position) comparator == stable sort by distance."""
    sd, sp = self_t[0], self_t[1]
    pd, pp = part_t[0], part_t[1]
    return (sd > pd) | ((sd == pd) & (sp > pp))


def _beam_hop_kernel(ids_ref, cand_ref, pi_ref, pd_ref, pv_ref, q_ref,
                     tab_ref, opi_ref, opd_ref, opv_ref, stats_ref,
                     tiles, rows, sem, slots, *lut_scr, dist_backend: str,
                     width: int):
    live = fetch_live_rows(ids_ref, tab_ref, tiles, rows, sem, slots)
    if dist_backend == "f32":
        nd = l2_scores(rows, q_ref[...])
    else:
        nd = lut_scores(rows, q_ref, *lut_scr, live=live)

    tb, ef = pi_ref.shape
    r = cand_ref.shape[1]
    pad = width - ef - r
    cand = cand_ref[...]                                  # -1 = invalid
    ids = jnp.concatenate(
        [pi_ref[...], cand, jnp.full((tb, pad), -1, jnp.int32)], axis=1)
    ds = jnp.concatenate(                         # hides unfetched rows
        [pd_ref[...], jnp.where(cand >= 0, nd, jnp.inf),
         jnp.full((tb, pad), jnp.inf, jnp.float32)], axis=1)
    vis = jnp.concatenate(
        [pv_ref[...], jnp.zeros((tb, r + pad), jnp.int32)], axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)
    is_cand = (lane >= ef) & (lane < ef + r)

    # rotating by s pairs lane i with lane i - s: over s = 1..ef+r-1 every
    # candidate lane meets every pool lane once
    dup = jnp.zeros(ids.shape, bool)
    for s in range(1, ef + r):
        from_pool = (lane >= s) & (lane < s + ef)
        dup = dup | ((ids == pltpu.roll(ids, s, 1)) & from_pool)
    dup = dup & is_cand
    live = is_cand & (ids >= 0)
    gathered = jnp.sum(live.astype(jnp.int32), axis=1, keepdims=True)
    n_dup = jnp.sum((dup & live).astype(jnp.int32), axis=1, keepdims=True)
    bad = is_cand & (dup | (ids < 0))
    ids = jnp.where(bad, -1, ids)
    ds = jnp.where(bad, jnp.inf, ds)

    ds, _, ids, vis = bitonic_by((ds, lane, ids, vis), _stable_gt, width)
    opi_ref[...] = ids[:, :ef]
    opd_ref[...] = ds[:, :ef]
    opv_ref[...] = vis[:, :ef]
    stats_ref[...] = jnp.concatenate([gathered, n_dup], axis=1)


@functools.partial(jax.jit, static_argnames=("dist_backend", "interpret"))
def beam_hop_pallas(sel: jax.Array, neighbors: jax.Array, pool_i: jax.Array,
                    pool_d: jax.Array, pool_v: jax.Array,
                    q_or_lut: jax.Array, table: jax.Array,
                    dist_backend: str = "f32",
                    interpret: bool = True):
    """One fused hop over all Q lanes; see ``ref.beam_hop_ref`` for shapes.

    ``q_or_lut`` is the (Q, D) queries, or for "pq"/"int8" the ADC table
    centroid-major, (Q, C, M) (``row_gather.centroid_major`` of the ref's
    (Q, M, C) one). ``table`` ((N, D) f32 db or (N, M) uint8 codes,
    optionally pre-padded by ``row_gather.pad_table``) stays in HBM; the
    kernel DMAs the tiles holding the live candidate rows of each query.
    Inactive lanes (sel < 0) mask every candidate and fetch and score
    nothing, so their pool state passes through unchanged (up to the
    already-applied visited mark).
    """
    nq, ef = pool_i.shape
    r = neighbors.shape[1]
    width = network_width(ef + r)
    nbr = neighbors[jnp.maximum(sel, 0)]
    cand = pad_block_rows(
        jnp.where((nbr >= 0) & (sel >= 0)[:, None], nbr, -1), -1)
    table = pad_table(table)
    pool_spec = pl.BlockSpec((TB, ef), lambda i: (i, 0))
    scratch = (row_scratch(r, table, quantized=dist_backend != "f32")
               + [slot_scratch(r)])
    if dist_backend == "f32":
        q_spec = pl.BlockSpec((TB, q_or_lut.shape[1]), lambda i: (i, 0))
    else:
        q_spec = pl.BlockSpec((TB,) + q_or_lut.shape[1:],
                              lambda i: (i, 0, 0))
        scratch += lut_scratch(r, q_or_lut.shape[2])
    nqp = cand.shape[0]
    kernel = functools.partial(_beam_hop_kernel, dist_backend=dist_backend,
                               width=width)
    opi, opd, opv, stats = pl.pallas_call(
        kernel,
        grid=(nqp // TB,),
        in_specs=[
            pl.BlockSpec((TB, r), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((TB, r), lambda i: (i, 0)),
            pool_spec, pool_spec, pool_spec,
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pool_spec, pool_spec, pool_spec,
                   pl.BlockSpec((TB, 2), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((nqp, ef), jnp.int32),
            jax.ShapeDtypeStruct((nqp, ef), jnp.float32),
            jax.ShapeDtypeStruct((nqp, ef), jnp.int32),
            jax.ShapeDtypeStruct((nqp, 2), jnp.int32),
        ],
        scratch_shapes=scratch,
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(cand, cand, pad_block_rows(pool_i, -1), pad_block_rows(pool_d, jnp.inf),
      pad_block_rows(pool_v.astype(jnp.int32), 1),
      pad_block_rows(q_or_lut, 0), table)
    return opi[:nq], opd[:nq], opv[:nq] != 0, stats[:nq]
