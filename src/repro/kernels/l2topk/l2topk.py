"""Fused L2-distance + running-top-k Pallas TPU kernel.

The paper's profile: >90% of NSG search time is L2 distance evaluation, and
the brute-force / kNN-graph-build / IVF paths all reduce to "score a query
tile against the database, keep the k best". This kernel streams database
blocks through VMEM, forms the distance tile on the MXU via
``|q|^2 - 2 q.x^T + |x|^2``, and maintains the running top-k in VMEM scratch —
the (Q, N) distance matrix never exists in HBM.

Top-k inside the kernel avoids `lax.top_k`/`sort` (unsupported in Pallas TPU
lowering): k is small (paper uses k=10), so we run k rounds of
(min, argmin, mask) over the block and a vectorized sorted-insertion into the
running list. Cost per block: k * O(TQ*TN) VPU ops vs the O(TQ*TN*D) MXU
matmul — negligible for D >= 64.

Grid: (Q/TQ, N/TN), db-block innermost ("arbitrary"); query tiles parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _insert_sorted(best_d, best_i, cand_d, cand_i):
    """Insert one candidate per row into a row-sorted (TQ, k) list."""
    k = best_d.shape[1]
    pos = jnp.sum((best_d < cand_d[:, None]).astype(jnp.int32), axis=1)
    idx = jax.lax.broadcasted_iota(jnp.int32, best_d.shape, 1)
    # value shifted one slot right (previous element), entry 0 irrelevant
    shift_d = jnp.concatenate([best_d[:, :1], best_d[:, :-1]], axis=1)
    shift_i = jnp.concatenate([best_i[:, :1], best_i[:, :-1]], axis=1)
    new_d = jnp.where(idx < pos[:, None], best_d,
                      jnp.where(idx == pos[:, None], cand_d[:, None],
                                shift_d))
    new_i = jnp.where(idx < pos[:, None], best_i,
                      jnp.where(idx == pos[:, None], cand_i[:, None],
                                shift_i))
    return new_d, new_i


def _l2topk_kernel(q_ref, db_ref, dn_ref, out_d_ref, out_i_ref,
                   best_d, best_i, *, k: int, block_n: int, n_total: int):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_d[...] = jnp.full_like(best_d[...], jnp.inf)
        best_i[...] = jnp.full_like(best_i[...], -1)

    q = q_ref[...].astype(jnp.float32)                    # (TQ, D)
    x = db_ref[...].astype(jnp.float32)                   # (TN, D)
    xn = dn_ref[...].astype(jnp.float32)                  # (1, TN) |x|^2
    qn = jnp.sum(q * q, axis=1, keepdims=True)            # (TQ, 1)
    # MXU: -2 q.x^T ; distances (TQ, TN)
    tile = qn + xn - 2.0 * jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    tile = jnp.maximum(tile, 0.0)
    col = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) + j * block_n
    tile = jnp.where(col < n_total, tile, jnp.inf)        # mask padding rows

    bd, bi = best_d[...], best_i[...]
    for _ in range(k):                                     # unrolled: k small
        cand_d = jnp.min(tile, axis=1)
        cand_a = jnp.argmin(tile, axis=1)
        cand_i = cand_a + j * block_n
        worse = cand_d >= bd[:, -1]
        nd, ni = _insert_sorted(bd, bi, cand_d, cand_i)
        bd = jnp.where(worse[:, None], bd, nd)
        bi = jnp.where(worse[:, None], bi, ni)
        # knock out the taken column
        hit = (jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
               == cand_a[:, None])
        tile = jnp.where(hit, jnp.inf, tile)
    best_d[...] = bd
    best_i[...] = bi

    @pl.when(j == nj - 1)
    def _emit():
        out_d_ref[...] = best_d[...]
        out_i_ref[...] = best_i[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_n", "interpret"))
def l2_topk_pallas(queries: jax.Array, database: jax.Array, k: int,
                   block_q: int = 128, block_n: int = 512,
                   interpret: bool = True):
    """(Q, D) x (N, D) -> (dists (Q, k) f32 ascending, ids (Q, k) i32).

    interpret=True on CPU (this container); False compiles for TPU.
    """
    q, d = queries.shape
    n = database.shape[0]
    block_q = min(block_q, q)
    block_n = min(block_n, n)
    gq = -(-q // block_q)
    gn = -(-n // block_n)
    qp = jnp.pad(queries, ((0, gq * block_q - q), (0, 0)))
    dbp = jnp.pad(database, ((0, gn * block_n - n), (0, 0)))
    db_norm = jnp.sum(dbp.astype(jnp.float32) ** 2, axis=1)[None, :]

    kernel = functools.partial(_l2topk_kernel, k=k, block_n=block_n,
                               n_total=n)
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=(gq, gn),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((gq * block_q, k), jnp.float32),
            jax.ShapeDtypeStruct((gq * block_q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qp, dbp, db_norm)
    return out_d[:q], out_i[:q]
