"""Candidate-row gathers and scores shared by the graph-search kernels.

``gather_dist``, ``lut_dist`` and ``beam_hop`` all work on a block of ``TB``
queries per grid step, each with R candidate ids, and need the R candidate
rows of an (N, W) HBM table in VMEM before they can score them.

Mosaic DMAs only tile-aligned windows of a tiled HBM array: whole 8-row
sublane tiles and whole 128-lane columns. So

  * the table is padded once to (ceil8(N), ceil128(W)) (``pad_table``, a
    no-op for an aligned table; callers that loop hoist it out of the
    loop), and
  * each candidate's DMA copies the 8-row tile that holds its row, and a
    one-hot sublane select extracts the row (exact: one value plus zeros).

The (TB, R) ids are a block in SMEM, where the scalar core reads them as
DMA addresses. Every DMA of the block is started before the first wait, so
the copies overlap. They share one DMA semaphore, and a wait on it only
counts bytes: the k-th wait proves that k copies' worth of bytes landed,
not that copy k did. So no tile is read until every copy has been waited
for.

The scores reproduce the jnp oracles bit for bit: the f32 score is the
diff-square ``sum((x - q)^2)`` over the unpadded width, and the LUT score
sums the M picked LUT entries left to right.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TB = 8            # queries per grid step: the sublane tile of (Q, *) blocks
TILE = 8          # table rows per DMA: the HBM sublane tile
LANES = 128
VMEM_LIMIT = 64 * 1024 * 1024


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_table(table: jax.Array) -> jax.Array:
    """Pad an (N, W) table to whole DMA tiles; no-op when already aligned."""
    n, w = table.shape
    pn, pw = round_up(n, TILE) - n, round_up(w, LANES) - w
    if pn or pw:
        table = jnp.pad(table, ((0, pn), (0, pw)))
    return table


def pad_block_rows(x: jax.Array, fill) -> jax.Array:
    """Pad the leading (query) axis to a multiple of ``TB`` with ``fill``."""
    p = round_up(x.shape[0], TB) - x.shape[0]
    if not p:
        return x
    return jnp.pad(x, ((0, p),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=fill)


def row_scratch(r: int, table: jax.Array, quantized: bool):
    """Scratch shapes for ``fetch_rows`` over TB x r candidates."""
    w = table.shape[1]
    return [pltpu.VMEM((TB * r, TILE, w), table.dtype),
            pltpu.VMEM((TB * r, w), jnp.int32 if quantized else jnp.float32),
            pltpu.SemaphoreType.DMA((1,))]


def lut_scratch(r: int, m: int):
    """Extra scratch for ``lut_scores`` over TB x r candidates."""
    return [pltpu.VMEM((TB * r, m), jnp.float32),
            pltpu.VMEM((m, TB * r), jnp.float32)]


def fetch_rows(ids_ref, table_ref, tiles, rows, sem):
    """rows[b * R + j] <- table[max(ids[b, j], 0)] for the block's ids."""
    tb, r = ids_ref.shape

    def row_id(t):
        return jnp.maximum(ids_ref[t // r, t % r], 0)

    def copy(t):
        base = pl.multiple_of(row_id(t) // TILE * TILE, TILE)
        return pltpu.make_async_copy(table_ref.at[pl.ds(base, TILE)],
                                     tiles.at[t], sem.at[0])

    def start(t, carry):
        copy(t).start()
        return carry

    def wait(t, carry):
        copy(t).wait()
        return carry

    def take(t, carry):
        tile = tiles[t].astype(rows.dtype)                   # (TILE, W)
        sub = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
        pick = jnp.where(sub == row_id(t) % TILE, tile, 0)
        rows[pl.ds(t, 1), :] = jnp.sum(pick, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, tb * r, start, 0)
    jax.lax.fori_loop(0, tb * r, wait, 0)
    jax.lax.fori_loop(0, tb * r, take, 0)


def l2_scores(rows, q):
    """(TB*R, W) f32 rows vs (TB, D) queries -> (TB, R) squared L2."""
    tb, d = q.shape
    x = rows[:, :d].reshape(tb, -1, d)
    diff = x - q.astype(jnp.float32)[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def lut_scores(rows, lut_ref, per_m, per_m_t):
    """(TB*R, W) int32 codes vs (TB, M, C) LUTs -> (TB, R) ADC distances.

    Candidate t's M picks (a one-hot select over C, summed: exact) land in
    row t of ``per_m``; the transpose puts subspaces on sublanes, so the
    left-to-right sum over M is M-1 adds of (1, TB*R) rows.
    """
    tb, m, c = lut_ref.shape
    n = rows.shape[0]

    def pick(t, carry):
        code = rows[pl.ds(t, 1), :m].reshape(m, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (m, c), 1)
        sel = jnp.where(lane == code, lut_ref[t // (n // tb)], 0.0)
        per_m[pl.ds(t, 1), :] = jnp.sum(sel, axis=1).reshape(1, m)
        return carry

    jax.lax.fori_loop(0, n, pick, 0)
    per_m_t[...] = per_m[...].T
    acc = jax.lax.fori_loop(
        1, m, lambda mm, a: a + per_m_t[pl.ds(mm, 1), :],
        per_m_t[pl.ds(0, 1), :])
    return acc.T.reshape(tb, n // tb)


def compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)
