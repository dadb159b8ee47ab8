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

Two fetches share those loops. ``fetch_rows`` (``gather_dist``,
``lut_dist``) fetches every slot and reads row 0 for a negative id, the
contract of those kernels. ``fetch_live_rows`` (``beam_hop``) fetches only
the slots whose id is >= 0: in the hop a negative id is a dead slot, which
its merge masks, and most of a hop's slots are dead.

The scores reproduce the jnp oracles bit for bit: the f32 score is the
diff-square ``sum((x - q)^2)`` over the unpadded width, and the LUT score
sums the M picked LUT entries left to right.

The LUT score picks entries by selection, not by gather: dynamic gathers
don't vectorize on the VPU. The kernels take the ADC table centroid-major,
(Q, C, M) (``centroid_major``), so centroid c's M sub-distances are one
lane row. For each query, ``lut_scores`` keeps its R code rows as one
(R, M) block and, for c = 0..C-1, selects row c of the table wherever a
code equals c: compares and selects over a few vregs, the row broadcast
across sublanes. Each (slot, m) is written by exactly one c, so every
pick is the LUT value itself. A query with no live slot can skip its
scoring (``fetch_live_rows``' count).
"""
from __future__ import annotations

import functools

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
    _fetch(tb * r, lambda k: k,
           lambda t: jnp.maximum(ids_ref[t // r, t % r], 0),
           table_ref, tiles, rows, sem)


def fetch_live_rows(ids_ref, table_ref, tiles, rows, sem, slots):
    """rows[b * R + j] <- table[ids[b, j]] where ids[b, j] >= 0 only.

    The rows of negative ids keep whatever they held: the caller masks
    their scores. A scalar pass lists each live slot and its id in
    ``slots`` (SMEM: TB * R slot numbers, then TB * R ids, then each
    query's live count), and the copies, waits and extracts run over the
    list. The pass is unrolled over a query's R ids, all loaded before
    the first store, so the scalar core need not wait on each load in
    turn; the copy loops read a listed id without first reading the slot
    that leads to it.

    Returns ``live``: b -> whether query b of the block has a live slot.
    """
    tb, r = ids_ref.shape
    n_all = tb * r

    def listed(b, n):
        ids = [ids_ref[b, j] for j in range(r)]
        n0 = n
        for j, i in enumerate(ids):
            slots[n] = b * r + j      # n <= b * r + j: kept if i is live
            slots[n_all + n] = i
            n = n + (i >= 0).astype(jnp.int32)
        slots[2 * n_all + b] = n - n0
        return n

    n = jax.lax.fori_loop(0, tb, listed, jnp.int32(0))
    _fetch(n, lambda k: slots[k], lambda k: slots[n_all + k],
           table_ref, tiles, rows, sem)
    return lambda b: slots[2 * n_all + b] > 0


def slot_scratch(r: int):
    """Scratch for ``fetch_live_rows``'s list of TB x r slots and ids and
    the TB live counts."""
    return pltpu.SMEM((2 * TB * r + TB,), jnp.int32)


def _fetch(n, slot, row_id, table_ref, tiles, rows, sem):
    """For k < n: copy, wait for and extract table row row_id(k) into
    rows[slot(k)]."""

    def copy(k):
        base = pl.multiple_of(row_id(k) // TILE * TILE, TILE)
        return pltpu.make_async_copy(table_ref.at[pl.ds(base, TILE)],
                                     tiles.at[slot(k)], sem.at[0])

    def start(k, carry):
        copy(k).start()
        return carry

    def wait(k, carry):
        copy(k).wait()
        return carry

    def take(k, carry):
        t = slot(k)
        tile = tiles[t].astype(rows.dtype)                   # (TILE, W)
        sub = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
        pick = jnp.where(sub == row_id(k) % TILE, tile, 0)
        rows[pl.ds(t, 1), :] = jnp.sum(pick, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)
    jax.lax.fori_loop(0, n, take, 0)


def l2_scores(rows, q):
    """(TB*R, W) f32 rows vs (TB, D) queries -> (TB, R) squared L2."""
    tb, d = q.shape
    x = rows[:, :d].reshape(tb, -1, d)
    diff = x - q.astype(jnp.float32)[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def centroid_major(lut: jax.Array) -> jax.Array:
    """A (Q, M, C) ADC table in the kernels' (Q, C, M) layout.

    One transpose, made once per search before its first hop: inside the
    hop loop XLA would copy the whole table on every hop.
    """
    return jnp.swapaxes(lut, 1, 2)


def lut_scores(rows, lut_ref, per_m, per_m_t, live=None):
    """(TB*R, W) int32 codes vs (TB, C, M) LUTs -> (TB, R) ADC distances.

    Query b's R code rows are one (R, M) block; for each centroid c its
    picks take row c of the query's table where the code is c (a select,
    so each pick is the LUT value). Rows of centroids load a sublane tile
    at a time. ``live`` (b -> bool), if given, skips the queries with
    nothing to score: their ``per_m`` rows keep stale values, which the
    caller masks. The transpose then puts subspaces on sublanes, so the
    left-to-right sum over M is M-1 adds of (1, TB*R) rows.
    """
    tb, c, m = lut_ref.shape
    r = rows.shape[0] // tb

    def score(b):
        codes = rows[pl.ds(b * r, r), :m]                     # (R, M)

        def pick(first, size, picked):
            table = lut_ref[b, pl.ds(first, size), :]         # (size, M)
            for i in range(size):
                picked = jnp.where(codes == first + i, table[i:i + 1, :],
                                   picked)
            return picked

        picked = jax.lax.fori_loop(
            0, c // TILE,
            lambda t, p: pick(pl.multiple_of(t * TILE, TILE), TILE, p),
            jnp.zeros((r, m), jnp.float32))
        if c % TILE:
            picked = pick(c - c % TILE, c % TILE, picked)
        per_m[pl.ds(b * r, r), :] = picked

    for b in range(tb):
        if live is None:
            score(b)
        else:
            pl.when(live(b))(functools.partial(score, b))
    per_m_t[...] = per_m[...].T
    acc = jax.lax.fori_loop(
        1, m, lambda mm, a: a + per_m_t[pl.ds(mm, 1), :],
        per_m_t[pl.ds(0, 1), :])
    return acc.T.reshape(tb, r)


def compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)
