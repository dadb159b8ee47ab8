"""Pure-jnp oracles for the topk_merge kernel.

``topk_merge_ref`` is the NN-Descent table merge (moved verbatim from
``core/build/nn_descent._merge`` — the 3-stable-argsort formulation), and
``topk_pool_ref`` is the NSG candidate-pool sort/dedup/truncate (the
argsort + ``mark_dups`` + argsort sequence ``core/nsg`` historically
inlined). The Pallas bitonic kernel must reproduce both; these stay the
default backend off-TPU, so CPU CI numbers are bit-identical to the
pre-kernel code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def mark_dups(ids: jax.Array) -> jax.Array:
    """True at positions holding a value already seen to the left."""
    eq = ids[:, :, None] == ids[:, None, :]                    # (B, L, L)
    tri = jnp.tril(jnp.ones(eq.shape[-2:], bool), k=-1)
    return jnp.any(eq & tri[None], axis=-1) | (ids < 0)


def topk_merge_ref(cur_i, cur_d, cur_f, cand_i, cand_d, k):
    """Merge (B, K) current rows with (B, M) candidates -> new top-k rows.

    Dedup keeps the *existing* copy of an id (fresh=False) so re-proposed
    neighbors are not resampled as new next round.
    """
    ids = jnp.concatenate([cur_i, cand_i], axis=1)
    ds = jnp.concatenate([cur_d, cand_d], axis=1)
    fresh = jnp.concatenate(
        [cur_f, jnp.ones(cand_i.shape, bool)], axis=1)
    # lexsort by (id, fresh): stable sort on the secondary key first
    ord0 = jnp.argsort(fresh, axis=1, stable=True)           # old copies first
    ids = jnp.take_along_axis(ids, ord0, axis=1)
    ds = jnp.take_along_axis(ds, ord0, axis=1)
    fresh = jnp.take_along_axis(fresh, ord0, axis=1)
    ord1 = jnp.argsort(ids, axis=1, stable=True)
    ids = jnp.take_along_axis(ids, ord1, axis=1)
    ds = jnp.take_along_axis(ds, ord1, axis=1)
    fresh = jnp.take_along_axis(fresh, ord1, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((ids.shape[0], 1), bool), ids[:, 1:] == ids[:, :-1]],
        axis=1)
    ds = jnp.where(dup | (ids < 0), jnp.inf, ds)
    ord2 = jnp.argsort(ds, axis=1, stable=True)[:, :k]
    out_i = jnp.take_along_axis(ids, ord2, axis=1)
    out_d = jnp.take_along_axis(ds, ord2, axis=1)
    out_f = jnp.take_along_axis(fresh, ord2, axis=1)
    out_i = jnp.where(jnp.isfinite(out_d), out_i, -1)
    out_f = out_f & (out_i >= 0)
    return out_i, out_d, out_f


def topk_pool_ref(ids, ds, k):
    """Distance-sort, dedup (nearest copy of an id wins), truncate to k.

    -1 ids and non-finite dists come back as (-1, inf) tail padding.
    """
    ds = jnp.where(ids < 0, jnp.inf, ds)
    order = jnp.argsort(ds, axis=1, stable=True)
    ids = jnp.take_along_axis(ids, order, axis=1)
    ds = jnp.take_along_axis(ds, order, axis=1)
    dup = mark_dups(ids)
    ids = jnp.where(dup, -1, ids)
    ds = jnp.where(dup, jnp.inf, ds)
    order = jnp.argsort(ds, axis=1, stable=True)[:, :k]
    out_i = jnp.take_along_axis(ids, order, axis=1)
    out_d = jnp.take_along_axis(ds, order, axis=1)
    return jnp.where(jnp.isfinite(out_d), out_i, -1), out_d
