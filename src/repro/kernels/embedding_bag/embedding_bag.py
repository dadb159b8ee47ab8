"""EmbeddingBag (sum/mean over a bag of rows) Pallas TPU kernel.

JAX has no native EmbeddingBag; the recsys models build theirs from
``jnp.take`` + ``segment_sum`` (see models/recsys_common.py). That XLA path
materializes the (B, L, D) gathered tensor in HBM. This kernel instead
accumulates rows in VMEM as they stream in via scalar-prefetch index maps —
HBM traffic drops from (B*L*D + B*L*D) to (B*L*D read + B*D write).

Grid: (B, L) — bag-member innermost, accumulated into the (1, D) out block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(ids_ref, w_ref, row_ref, out_ref, *, bag: int,
                combiner: str):
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref[...])

    i = pl.program_id(0)
    w = w_ref[i, l].astype(jnp.float32)
    out_ref[...] += w * row_ref[...].astype(jnp.float32)

    if combiner == "mean":
        @pl.when(l == bag - 1)
        def _norm():
            denom = jnp.maximum(jnp.sum(w_ref[i, :].astype(jnp.float32)),
                                1e-9)
            out_ref[...] = out_ref[...] / denom


@functools.partial(jax.jit, static_argnames=("combiner", "interpret"))
def embedding_bag_pallas(table: jax.Array, ids: jax.Array,
                         weights: jax.Array | None = None,
                         combiner: str = "sum",
                         interpret: bool = True) -> jax.Array:
    """table (V, D), ids (B, L) int32 (-1 pads) -> (B, D) f32.

    weights: optional (B, L); padding ids get weight 0 regardless.
    """
    b, bag = ids.shape
    v, d = table.shape
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    weights = jnp.where(ids >= 0, weights, 0.0).astype(jnp.float32)
    safe = jnp.maximum(ids, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # ids, weights
        grid=(b, bag),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, j, ids_ref, w_ref:
                         (ids_ref[i, j], 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, j, ids_ref, w_ref: (i, 0)),
    )
    kernel = functools.partial(_bag_kernel, bag=bag, combiner=combiner)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(safe, weights, table)
