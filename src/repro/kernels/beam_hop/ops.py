"""Backend dispatch for the fused beam hop (graph-traversal hot path)."""
from __future__ import annotations

import jax

from repro.kernels.beam_hop.beam_hop import beam_hop_pallas
from repro.kernels.beam_hop.ref import beam_hop_ref


def beam_hop(sel: jax.Array, neighbors: jax.Array, pool_i: jax.Array,
             pool_d: jax.Array, pool_v: jax.Array, q_or_lut: jax.Array,
             table: jax.Array, dist_backend: str = "f32",
             backend: str = "jnp", **kw):
    """One fused hop -> (pool_i, pool_d, pool_v, stats (Q, 2) int32).

    A quantized ``q_or_lut`` is the (Q, M, C) ADC table for "jnp" and its
    ``row_gather.centroid_major`` (Q, C, M) layout for "pallas"."""
    if backend == "jnp":
        return beam_hop_ref(sel, neighbors, pool_i, pool_d, pool_v,
                            q_or_lut, table, dist_backend=dist_backend)
    if backend == "pallas":
        kw.setdefault("interpret", jax.default_backend() != "tpu")
        return beam_hop_pallas(sel, neighbors, pool_i, pool_d, pool_v,
                               q_or_lut, table, dist_backend=dist_backend,
                               **kw)
    raise ValueError(f"unknown backend {backend!r}")
