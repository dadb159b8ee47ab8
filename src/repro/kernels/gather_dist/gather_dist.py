"""Gather + L2 distance Pallas TPU kernel.

The inner loop of graph traversal: given the (B, R) neighbor ids of the
nodes being expanded, fetch those db rows and score them against each
query. On CPU (Faiss) this is R scalar gathers + R scalar distance loops
per query; here each grid step takes ``TB`` queries, reads their (TB, R)
ids from SMEM as DMA addresses, streams the candidate rows HBM->VMEM with
overlapping DMAs (``row_gather.fetch_rows``) and scores the (TB, R) block
in one vectorized diff-square reduction.

Grid: (ceil(B / TB),).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.row_gather import (
    TB, compiler_params, fetch_rows, l2_scores, pad_block_rows, pad_table,
    row_scratch,
)


def _gather_dist_kernel(ids_ref, q_ref, tab_ref, out_ref, tiles, rows, sem):
    fetch_rows(ids_ref, tab_ref, tiles, rows, sem)
    out_ref[...] = l2_scores(rows, q_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_dist_pallas(queries: jax.Array, db: jax.Array, ids: jax.Array,
                       interpret: bool = True) -> jax.Array:
    """queries (B, D), db (N, D), ids (B, R) int32 -> (B, R) f32 sq-dists.

    ``db`` may arrive already padded by ``row_gather.pad_table`` (wider
    than D): only its first D columns are scored. Negative ids read row 0
    and are masked to +inf outside the kernel (beam_search's padding
    convention).
    """
    b, d = queries.shape
    r = ids.shape[1]
    table = pad_table(db)
    ids_p = pad_block_rows(ids, -1)
    out = pl.pallas_call(
        _gather_dist_kernel,
        grid=(ids_p.shape[0] // TB,),
        in_specs=[
            pl.BlockSpec((TB, r), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((TB, d), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((TB, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(ids_p.shape, jnp.float32),
        scratch_shapes=row_scratch(r, table, quantized=False),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(ids_p, pad_block_rows(queries, 0), table)
    return jnp.where(ids >= 0, out[:b], jnp.inf)
