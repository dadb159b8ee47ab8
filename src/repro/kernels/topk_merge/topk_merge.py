"""Bitonic top-k merge Pallas TPU kernel.

NN-Descent's update step and NSG's candidate-pool assembly both reduce to
the same primitive: given per-row candidate lists (ids, dists[, fresh]),
drop duplicate ids, and keep the k best by distance. The jnp formulation
(``ref.py``) spends three stable argsorts per row block — cheap on TPU's
sort unit, dominant on a 1-core CPU host, and `lax.sort` does not lower
inside Pallas TPU kernels at all. This kernel restates the primitive as a
bitonic sorting network over VMEM-resident row blocks:

  1. sort lanes by a lexicographic dedup key — padding ids (< 0) map to
     an int32 sentinel so they sink to the tail;
  2. mark lanes whose id equals their left neighbor's (a run of equal ids
     is contiguous after the sort, and its first lane is the kept copy);
  3. re-sort by distance and emit the first k lanes.

The compare-exchange partner ``i XOR j`` comes from two lane rotations
(``kernels/bitonic``). Both sorts run the full O(M log^2 M) network,
vectorized across the block's rows on the VPU; M (the candidate width,
padded to a power of two of at least 128 lanes) is small (a few hundred),
so the network cost is noise next to the MXU distance tiles that produced
the candidates.

Every key ends in the input position, so the network reproduces the
reference's stable argsorts exactly, ties included:

  * table merge (``topk_merge``): the kept copy of an id is the old one
    if any, else the first in input order (key id, fresh, position) —
    copies of one pair may carry distances from different arithmetic
    (block-join tiles vs local-join rounds), so the copy matters; the
    output ties on distance break by id;
  * pool assembly (``nearest=True``, ``topk_pool``): the kept copy is the
    nearest, then the first (key id, distance, position); output ties on
    distance break by input position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitonic import bitonic_by, network_width
from repro.kernels.row_gather import compiler_params, round_up

_I32_MAX = jnp.iinfo(jnp.int32).max


def _lex_gt(keys_s, keys_p):
    """Strict lexicographic "self after partner" over key tuples."""
    gt = keys_s[-1] > keys_p[-1]
    for s, p in zip(keys_s[-2::-1], keys_p[-2::-1]):
        gt = (s > p) | ((s == p) & gt)
    return gt


def _id_key(ids):
    return jnp.where(ids < 0, _I32_MAX, ids)


# payload tuples are (ids, dists, fresh, position)
def _dedup_old_gt(s, p):
    return _lex_gt((_id_key(s[0]), s[2], s[3]), (_id_key(p[0]), p[2], p[3]))


def _dedup_nearest_gt(s, p):
    return _lex_gt((_id_key(s[0]), s[1], s[3]), (_id_key(p[0]), p[1], p[3]))


def _dist_id_gt(s, p):
    return _lex_gt((s[1], _id_key(s[0])), (p[1], _id_key(p[0])))


def _dist_pos_gt(s, p):
    return _lex_gt((s[1], s[3]), (p[1], p[3]))


def _topk_merge_kernel(ci_ref, cd_ref, cf_ref, oi_ref, od_ref, of_ref, *,
                       k: int, m: int, nearest: bool):
    ids = ci_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)
    arrays = (ids, cd_ref[...], cf_ref[...], lane)    # fresh is int32 0/1

    ids, ds, fresh, pos = bitonic_by(
        arrays, _dedup_nearest_gt if nearest else _dedup_old_gt, m)
    prev = jnp.where(lane == 0, -2, pltpu.roll(ids, 1, 1))
    dup = (ids == prev) | (ids < 0)
    ds = jnp.where(dup, jnp.inf, ds)
    ids, ds, fresh, _ = bitonic_by(
        (ids, ds, fresh, pos), _dist_pos_gt if nearest else _dist_id_gt, m)

    out_i = jnp.where(jnp.isfinite(ds[:, :k]), ids[:, :k], -1)
    oi_ref[...] = out_i
    od_ref[...] = ds[:, :k]
    of_ref[...] = jnp.where(out_i >= 0, fresh[:, :k], 0)


@functools.partial(jax.jit,
                   static_argnames=("k", "nearest", "block_rows",
                                    "interpret"))
def topk_merge_pallas(ids: jax.Array, dists: jax.Array, fresh: jax.Array,
                      k: int, nearest: bool = False, block_rows: int = 64,
                      interpret: bool = True):
    """(B, M) candidate rows -> dedup'd distance-top-k (ids, dists, fresh).

    ``ids`` int32 (-1 = padding), ``dists`` f32, ``fresh`` bool.
    ``nearest`` picks the pool-assembly dedup (see the module doc). Rows are
    independent; the grid tiles them in blocks of ``block_rows`` (rounded
    up to a multiple of 8, the sublane tile). M is padded to the network
    width internally. interpret=True runs the kernel on CPU; False
    compiles it for TPU.
    """
    b, m_in = ids.shape
    m = network_width(max(m_in, k))
    block_rows = round_up(min(block_rows, b), 8)
    gb = -(-b // block_rows)
    padr = gb * block_rows - b
    pad = ((0, padr), (0, m - m_in))
    ids = jnp.pad(ids, pad, constant_values=-1)
    dists = jnp.pad(dists.astype(jnp.float32), pad, constant_values=jnp.inf)
    fresh = jnp.pad(fresh.astype(jnp.int32), pad, constant_values=0)

    kernel = functools.partial(_topk_merge_kernel, k=k, m=m,
                               nearest=nearest)
    in_spec = pl.BlockSpec((block_rows, m), lambda i: (i, 0))
    out_spec = pl.BlockSpec((block_rows, k), lambda i: (i, 0))
    out_i, out_d, out_f = pl.pallas_call(
        kernel,
        grid=(gb,),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((gb * block_rows, k), jnp.int32),
            jax.ShapeDtypeStruct((gb * block_rows, k), jnp.float32),
            jax.ShapeDtypeStruct((gb * block_rows, k), jnp.int32),
        ],
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(ids, dists, fresh)
    return out_i[:b], out_d[:b], out_f[:b] != 0
