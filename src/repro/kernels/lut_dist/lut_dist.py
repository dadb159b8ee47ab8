"""Code-gather + LUT accumulation Pallas TPU kernel.

The quantized twin of ``kernels/gather_dist``: the beam hop scores R
neighbors per query, but instead of streaming R f32 rows of D*4 bytes it
streams R uint8 code rows of M bytes and accumulates the per-query LUT —
the ADC inner loop of PQ/SQ8 traversal (VSAG/ScaNN-style). Each grid step
takes ``TB`` queries: their (TB, R) ids are read from SMEM as DMA
addresses (``row_gather.fetch_rows``) while the (TB, C, M) centroid-major
LUT block stays resident in VMEM.

The LUT entry pick is a select, not an in-kernel gather (dynamic gathers
don't vectorize on the VPU): for each centroid c, one LUT row is selected
into a query's (R, M) block of picks wherever its codes equal c, so each
pick is the LUT value itself. The M picks are then summed left to right
(``row_gather.lut_scores``), the order the jnp ref pins, keeping the
kernel bit-identical to it.

Grid: (ceil(Q / TB),).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.row_gather import (
    TB, compiler_params, fetch_rows, lut_scores, lut_scratch, pad_block_rows,
    pad_table, row_scratch,
)


def _lut_dist_kernel(ids_ref, lut_ref, tab_ref, out_ref, tiles, rows, sem,
                     per_m, per_m_t):
    fetch_rows(ids_ref, tab_ref, tiles, rows, sem)
    out_ref[...] = lut_scores(rows, lut_ref, per_m, per_m_t)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lut_dist_pallas(lut: jax.Array, codes: jax.Array, ids: jax.Array,
                    interpret: bool = True) -> jax.Array:
    """lut (Q, C, M) f32, codes (N, M) uint8, ids (Q, R) int32 -> (Q, R).

    ``lut`` is the ADC table centroid-major (``row_gather.centroid_major``
    of ``lut_dist_ref``'s (Q, M, C) one). ``codes`` may arrive already
    padded by ``row_gather.pad_table``.
    Negative ids read row 0 and are masked to +inf outside the kernel
    (beam_search's padding convention).
    """
    q, c, m = lut.shape
    r = ids.shape[1]
    table = pad_table(codes)
    ids_p = pad_block_rows(ids, -1)
    out = pl.pallas_call(
        _lut_dist_kernel,
        grid=(ids_p.shape[0] // TB,),
        in_specs=[
            pl.BlockSpec((TB, r), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((TB, c, m), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((TB, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(ids_p.shape, jnp.float32),
        scratch_shapes=(row_scratch(r, table, quantized=True)
                        + lut_scratch(r, m)),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(ids_p, pad_block_rows(lut, 0.0), table)
    return jnp.where(ids >= 0, out[:q], jnp.inf)
