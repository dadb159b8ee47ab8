"""Bitonic sorting-network building blocks shared by the Pallas kernels.

`lax.sort` does not lower inside Pallas TPU kernels, so every in-kernel
sort (``topk_merge``'s dedup-top-k, ``beam_hop``'s pool merge) is a bitonic
network over VMEM-resident lane blocks. The compare-exchange partner
``i XOR j`` (j a power of two) is lane ``i + j`` where bit j of i is clear
and lane ``i - j`` where it is set: two lane rotations (``pltpu.roll``, the
TPU's native rotate) and a select on ``lane & j``. Mosaic lowers rotates,
selects and iotas; it does not lower ``rev``, so a reshape-flip partner
fails to compile on TPU.

Rotations work on any lane width, but the networks here always run on a
width that is a power of two and at least one full 128-lane vreg
(``network_width``).

This module has no intra-repo imports on purpose: kernel packages can pull
it in without touching ``core`` (whose import graph reaches back into the
kernel packages' dispatchers).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def xor_partner(x, j):
    """Lanes i and i^j exchanged along axis 1 (j a power of two)."""
    m = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane & j) == 0, pltpu.roll(x, m - j, 1),
                     pltpu.roll(x, j, 1))


def bitonic_by(arrays, gt_fn, m):
    """Bitonic-sort (B, m) lane tuples ascending by a strict comparator.

    ``gt_fn(self_tuple, partner_tuple) -> bool (B, m)`` must be a strict
    "self sorts after partner" predicate (False on equal keys: equal-key
    lanes never swap, so payload fields not in the key ride along).
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, arrays[0].shape, 1)
    ksz = 2
    while ksz <= m:
        j = ksz // 2
        while j >= 1:
            partners = tuple(xor_partner(a, j) for a in arrays)
            gt_sp = gt_fn(arrays, partners)        # self > partner
            # partner-side verdict, moved to this lane
            gt_ps = xor_partner(gt_sp.astype(jnp.int32), j) != 0
            lo = (lane & j) == 0                   # lane is the pair's low i
            asc = (lane & ksz) == 0                # ascending sub-sequence
            # a select between bool vectors does not lower: spell it out
            own = lo == asc
            take = (own & gt_sp) | (~own & gt_ps)
            arrays = tuple(jnp.where(take, p, a)
                           for a, p in zip(arrays, partners))
            j //= 2
        ksz *= 2
    return arrays


def pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def network_width(x: int) -> int:
    """Lane width of a network over ``x`` entries: a power of two, >= 128."""
    return max(LANES, pow2_at_least(x))
