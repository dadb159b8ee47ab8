"""The plain reference: exact squared-L2 top-k by brute force.

Plain ``jax.numpy`` over the raw corpus rows, in blocks of queries and of
rows with a running top-k, float32 products under
``Precision.HIGHEST``. It imports nothing of the program under test.

``precision="bf16x3"`` is the control: the same search with every product
taken as three bfloat16 passes (hi*hi + hi*lo + lo*hi, the arithmetic of
``Precision.HIGH`` on a TPU), written out so that it rounds the same way
on any backend.

``exact_sqdist`` recomputes chosen distances in float64 on the host: the
yardstick for the distances a search reports.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _split_bf16(x: jax.Array):
    """x = hi + lo + rest, hi and lo rounded to bfloat16's 8-bit mantissa.

    ``reduce_precision`` rounds in place; a cast to bfloat16 and back is
    a pair the compiler may drop as excess precision.
    """
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _dot(q: jax.Array, x: jax.Array, precision: str) -> jax.Array:
    """(Q, D) x (B, D) -> (Q, B) inner products."""
    if precision == "highest":
        return jnp.matmul(q, x.T, precision=HIGHEST)
    if precision == "bf16x3":
        q_hi, q_lo = _split_bf16(q)
        x_hi, x_lo = _split_bf16(x)
        mm = functools.partial(jnp.matmul, precision=HIGHEST)
        return mm(q_hi, x_hi.T) + mm(q_hi, x_lo.T) + mm(q_lo, x_hi.T)
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("k", "row_block", "precision"))
def _topk_block(queries, data, k: int, row_block: int, precision: str):
    n = data.shape[0]
    row_block = min(row_block, n)
    n_blocks = -(-n // row_block)
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)

    def body(b, carry):
        best_d, best_i = carry
        nominal = b * row_block
        start = jnp.minimum(nominal, n - row_block)   # last block overlaps
        x = jax.lax.dynamic_slice_in_dim(data, start, row_block)
        ids = start + jnp.arange(row_block, dtype=jnp.int32)
        d = qn + jnp.sum(x * x, axis=1)[None, :] - 2.0 * _dot(queries, x,
                                                              precision)
        d = jnp.where(ids[None, :] >= nominal, d, jnp.inf)  # seen already
        all_d = jnp.concatenate([best_d, d], axis=1)
        all_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, d.shape)], axis=1)
        neg, pos = jax.lax.top_k(-all_d, k)
        return -neg, jnp.take_along_axis(all_i, pos, axis=1)

    q = queries.shape[0]
    init = (jnp.full((q, k), jnp.inf, jnp.float32),
            jnp.full((q, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, n_blocks, body, init)


def search(queries, data: jax.Array, k: int, *, precision: str = "highest",
           query_block: int = 1024, row_block: int = 16384):
    """Exact top-k of every query row: host (dists (Q, k), ids (Q, k))."""
    queries = np.asarray(queries, np.float32)
    out_d, out_i = [], []
    for s in range(0, queries.shape[0], query_block):
        blk = queries[s:s + query_block]
        n = blk.shape[0]
        if n < query_block:        # one compiled shape for every block
            blk = np.concatenate(
                [blk, np.repeat(blk[:1], query_block - n, axis=0)])
        d, i = _topk_block(jnp.asarray(blk), data, k, row_block, precision)
        out_d.append(np.asarray(d)[:n])
        out_i.append(np.asarray(i)[:n])
    return np.concatenate(out_d), np.concatenate(out_i)


@jax.jit
def _take(data, ids):
    return data[ids]


def exact_sqdist(queries: np.ndarray, data: jax.Array, ids: np.ndarray,
                 block: int = 512) -> np.ndarray:
    """float64 squared distances of ``queries[r]`` to ``data[ids[r, j]]``.

    Ids below 0 give NaN. Rows are gathered on the device in blocks and
    compared on the host in float64.
    """
    ids = np.asarray(ids)
    out = np.full(ids.shape, np.nan)
    for s in range(0, ids.shape[0], block):
        blk = ids[s:s + block]
        n = blk.shape[0]
        if n < block:
            blk = np.concatenate([blk, np.zeros((block - n,) + blk.shape[1:],
                                                blk.dtype)])
        rows = np.asarray(_take(data, jnp.asarray(np.maximum(blk, 0))),
                          np.float64)[:n]
        q = np.asarray(queries[s:s + n], np.float64)[:, None, :]
        out[s:s + n] = np.sum((rows - q) ** 2, axis=2)
    out[ids < 0] = np.nan
    return out
