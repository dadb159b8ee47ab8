"""The benchmark's own corpus and query generators.

A copy of ``repro.data.synthetic.clustered_vectors`` and ``queries_like``
(same arithmetic, same key splits), kept here so that a change to the
program cannot change the data it is measured on. The corpus stands in
for the LAION2B-en CLIP rows of SISAP 2023 Task A: a Gaussian mixture
with Zipf-like cluster weights and a decaying spectrum at the source's
width. Both are single jitted calls, so they run on the device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "dim", "n_clusters",
                                             "spectrum_decay"))
def clustered_vectors(key: jax.Array, n: int, dim: int, n_clusters: int = 64,
                      spectrum_decay: float = 0.95) -> jax.Array:
    """(n, dim) float32 rows of a skewed, anisotropic Gaussian mixture."""
    k_c, k_w, k_a, k_n, k_s = jax.random.split(key, 5)
    scales = spectrum_decay ** jnp.arange(dim, dtype=jnp.float32)
    centers = jax.random.normal(k_c, (n_clusters, dim)) * scales[None, :]
    w = 1.0 / (1.0 + jnp.arange(n_clusters, dtype=jnp.float32))
    w = w / jnp.sum(w)
    assign = jax.random.choice(k_a, n_clusters, (n,), p=w)
    noise = jax.random.normal(k_n, (n, dim)) * scales[None, :]
    return (centers[assign] + noise).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_queries", "jitter"))
def queries_like(key: jax.Array, data: jax.Array, n_queries: int,
                 jitter: float = 0.05) -> jax.Array:
    """In-distribution queries: database rows plus isotropic jitter."""
    k_i, k_n = jax.random.split(key)
    idx = jax.random.randint(k_i, (n_queries,), 0, data.shape[0])
    noise = jax.random.normal(k_n, (n_queries, data.shape[1]), data.dtype)
    return data[idx] + jitter * noise


def corpus(spec: dict) -> jax.Array:
    """The configuration's corpus, from its own fixed seed."""
    return clustered_vectors(jax.random.PRNGKey(spec["seed"]), spec["n"],
                             spec["dim"], spec["n_clusters"],
                             spec["spectrum_decay"])
