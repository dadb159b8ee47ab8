"""k-means (kmeans++ init + Lloyd) — entry-point clustering (paper §3.1, knob k).

Also reused by the IVF baseline's coarse quantizer and PQ codebook training
(``kmeans_blocks``: one k-means per column block, all in one program). All
distance work routes through the MXU-friendly chunked path.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.distances import l2_topk, pairwise_sqdist


class KMeansResult(NamedTuple):
    centroids: jax.Array     # (k, D)
    assignments: jax.Array   # (N,) int32
    inertia: jax.Array       # scalar, mean squared distance


@functools.partial(jax.jit, static_argnames=("k",))
def _kmeanspp_init(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    n = x.shape[0]
    key0, key = jax.random.split(key)
    first = jax.random.randint(key0, (), 0, n)
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    mind = pairwise_sqdist(x[first][None, :], x)[0]           # (N,)

    def body(i, carry):
        cents, mind, key = carry
        key, sub = jax.random.split(key)
        p = mind / jnp.maximum(jnp.sum(mind), 1e-12)
        nxt = jax.random.choice(sub, n, p=p)
        cents = cents.at[i].set(x[nxt])
        nd = pairwise_sqdist(x[nxt][None, :], x)[0]
        return cents, jnp.minimum(mind, nd), key

    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents, mind, key))
    return cents


@functools.partial(jax.jit, static_argnames=("k", "iters", "chunk"))
def _lloyd(key, x, k: int, iters: int, chunk: int):
    cents = _kmeanspp_init(key, x, k)
    n, d = x.shape

    def step(cents, _):
        _, assign = l2_topk(x, cents, 1, chunk=chunk)
        assign = assign[:, 0]
        sums = jax.ops.segment_sum(x, assign, num_segments=k)
        cnts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), assign,
                                   num_segments=k)
        new = sums / jnp.maximum(cnts, 1.0)[:, None]
        # keep empty clusters where they were
        new = jnp.where((cnts > 0)[:, None], new, cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    dists, assign = l2_topk(x, cents, 1, chunk=chunk)
    return cents, assign[:, 0], jnp.mean(dists[:, 0])


def kmeans(key: jax.Array, x: jax.Array, k: int, iters: int = 10,
           chunk: int = 16384) -> KMeansResult:
    if k < 1 or k > x.shape[0]:
        raise ValueError(f"k={k} out of range for n={x.shape[0]}")
    cents, assign, inertia = _lloyd(key, x, k, iters, chunk)
    return KMeansResult(cents, assign, inertia)


@functools.partial(jax.jit, static_argnames=("m", "k", "iters", "chunk"))
def _lloyd_blocks(key, x, m: int, k: int, iters: int, chunk: int):
    dsub = x.shape[1] // m

    def one(j):
        xj = jax.lax.dynamic_slice_in_dim(x, j * dsub, dsub, axis=1)
        return _lloyd(jax.random.fold_in(key, j), xj, k, iters, chunk)

    return jax.lax.map(one, jnp.arange(m))


def kmeans_blocks(key: jax.Array, x: jax.Array, m: int, k: int,
                  iters: int = 10, chunk: int = 16384) -> KMeansResult:
    """k-means of each of the ``m`` equal column blocks of ``x`` (N, D).

    Block j is ``kmeans(fold_in(key, j), x[:, j*D/m:(j+1)*D/m], k, iters)``,
    the same arithmetic, but the blocks run one after another inside one
    program: no program per block, and no slice compiled per block (PQ
    codebook training). Returns centroids (m, k, D/m), assignments (m, N),
    inertia (m,).
    """
    if x.shape[1] % m:
        raise ValueError(f"m={m} does not divide D={x.shape[1]}")
    if k < 1 or k > x.shape[0]:
        raise ValueError(f"k={k} out of range for n={x.shape[0]}")
    return KMeansResult(*_lloyd_blocks(key, x, m, k, iters, chunk))
