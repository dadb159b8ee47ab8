"""Backend dispatch for quantized LUT distances (quantized-traversal hot path)."""
from __future__ import annotations

import jax

from repro.kernels.lut_dist.lut_dist import lut_dist_pallas
from repro.kernels.lut_dist.ref import lut_dist_ref


def lut_dist(lut: jax.Array, codes: jax.Array, ids: jax.Array,
             backend: str = "jnp", **kw) -> jax.Array:
    """ADC distances (Q, R). ``lut`` is the (Q, M, C) table for "jnp" and
    its ``row_gather.centroid_major`` (Q, C, M) layout for "pallas"."""
    if backend == "jnp":
        return lut_dist_ref(lut, codes, ids)
    if backend == "pallas":
        kw.setdefault("interpret", jax.default_backend() != "tpu")
        return lut_dist_pallas(lut, codes, ids, **kw)
    raise ValueError(f"unknown backend {backend!r}")
