"""Chunked exact L2 distance + top-k — the paper's measured hotspot.

The paper profiles Faiss NSG and finds >90% of search time in L2 distance
evaluation; everything in this module is therefore written to run through
matmuls (MXU-friendly ``|q|^2 - 2 q.x + |x|^2``) with a running top-k merge so
the full (Q, N) distance matrix never materializes in HBM.

This is also the pure-jnp oracle for ``kernels/l2topk``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def match_vma(x: jax.Array, *refs: jax.Array) -> jax.Array:
    """Give constant-valued ``x`` the joint varying-manual-axes type of refs.

    Under shard_map (JAX 0.8 VMA typing), loop carries must be uniformly
    varying; freshly created constants are not. Adding a varying zero fixes
    the type without changing the value and folds away in XLA.
    """
    z = None
    for ref in refs:
        r = jnp.reshape(ref, (-1,))[0] * 0
        z = r if z is None else z + r.astype(z.dtype)
    if x.dtype == jnp.bool_:
        return x ^ (z != 0)
    return x + z.astype(x.dtype)


# Distance matmuls rank neighbors, so they run with f32 products: a TPU's
# default precision rounds f32 matmul operands to bf16. CPU ignores it.
EXACT = jax.lax.Precision.HIGHEST


def pairwise_sqdist(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared L2 distances. q: (Q, D), x: (N, D) -> (Q, N)."""
    # accumulate in f32 even for bf16 inputs: the -2qx term cancels
    # catastrophically near duplicates otherwise.
    q32 = q.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    qn = jnp.sum(q32 * q32, axis=-1, keepdims=True)          # (Q, 1)
    xn = jnp.sum(x32 * x32, axis=-1)                          # (N,)
    d = qn + xn[None, :] - 2.0 * jnp.matmul(q32, x32.T, precision=EXACT)
    return jnp.maximum(d, 0.0)


def _merge_topk(best_d, best_i, cand_d, cand_i, k):
    """Merge running (Q,k) top-k with candidate (Q,c) block; smallest-k."""
    d = jnp.concatenate([best_d, cand_d], axis=1)
    i = jnp.concatenate([best_i, cand_i], axis=1)
    # lax.top_k selects largest -> negate
    nd, pos = jax.lax.top_k(-d, k)
    return -nd, jnp.take_along_axis(i, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def l2_topk(queries: jax.Array, database: jax.Array, k: int,
            chunk: int = 16384):
    """Exact k smallest L2^2 distances of each query against the database.

    Returns (dists (Q,k) f32 ascending, ids (Q,k) i32). Database is scanned in
    ``chunk``-row blocks with a running top-k (streaming, memory O(Q*chunk)).
    """
    n, d = database.shape
    q = queries.shape[0]
    k = min(k, n)
    chunk = min(chunk, n)       # a small database is one unpadded block
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    db = jnp.pad(database, ((0, pad), (0, 0)))
    db = db.reshape(n_chunks, chunk, d)

    init_d = match_vma(jnp.full((q, k), jnp.inf, jnp.float32), queries,
                       database)
    init_i = match_vma(jnp.full((q, k), -1, jnp.int32), queries, database)

    def step(carry, inp):
        best_d, best_i = carry
        blk, start = inp
        cd = pairwise_sqdist(queries, blk)                    # (Q, chunk)
        ci = start + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        ci = jnp.broadcast_to(ci, cd.shape)
        cd = jnp.where(ci < n, cd, jnp.inf)                   # mask padding
        return _merge_topk(best_d, best_i, cd, ci, k), None

    starts = (jnp.arange(n_chunks, dtype=jnp.int32) * chunk)
    (best_d, best_i), _ = jax.lax.scan(step, (init_d, init_i), (db, starts))
    return best_d, best_i


@functools.partial(jax.jit, static_argnames=("chunk",))
def nearest(queries: jax.Array, database: jax.Array, chunk: int = 16384):
    """argmin-L2 id and distance per query (k=1 fast path)."""
    d, i = l2_topk(queries, database, 1, chunk=chunk)
    return d[:, 0], i[:, 0]
