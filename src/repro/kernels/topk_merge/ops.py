"""Backend dispatch for the dedup + distance-top-k primitive.

``topk_merge`` (NN-Descent table update: current rows + proposal
candidates, old copies win dedup) and ``topk_pool`` (NSG pool assembly:
one candidate list, nearest copy wins) both route here. Backend
``"jnp"`` is the stable-argsort reference — the default off-TPU, where
XLA's sort is fine and Pallas interpret mode would be pure overhead;
``"pallas"`` is the bitonic network kernel (interpret mode when no TPU is
attached, compiled otherwise). ``None`` picks by platform.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.topk_merge.ref import topk_merge_ref, topk_pool_ref
from repro.kernels.topk_merge.topk_merge import topk_merge_pallas

_BACKENDS = ("jnp", "pallas")


def resolve_merge_backend(backend: Optional[str]) -> str:
    """None -> "pallas" on TPU, "jnp" elsewhere; validate the name."""
    if backend is None:
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in _BACKENDS:
        raise ValueError(
            f"unknown merge backend {backend!r}; expected one of "
            f"{_BACKENDS} or None")
    return backend


def topk_merge(cur_i, cur_d, cur_f, cand_i, cand_d, k: int,
               backend: Optional[str] = None, **kw):
    """Merge (B, K) table rows with (B, M) candidates -> top-k rows.

    Candidates are implicitly fresh; dedup keeps the existing (old) copy
    of an id. Returns (ids, dists, fresh), -1/inf padded.
    """
    backend = resolve_merge_backend(backend)
    if backend == "jnp":
        return topk_merge_ref(cur_i, cur_d, cur_f, cand_i, cand_d, k)
    ids = jnp.concatenate([cur_i, cand_i], axis=1)
    ds = jnp.concatenate([cur_d, cand_d], axis=1)
    fresh = jnp.concatenate([cur_f, jnp.ones(cand_i.shape, bool)], axis=1)
    kw.setdefault("interpret", jax.default_backend() != "tpu")
    return topk_merge_pallas(ids, ds, fresh, k, **kw)


def topk_pool(ids, ds, k: int, backend: Optional[str] = None, **kw):
    """Distance-sort + dedup (nearest copy wins) + truncate to k.

    Returns (ids, dists); invalid tail entries come back as (-1, inf).
    """
    backend = resolve_merge_backend(backend)
    if backend == "jnp":
        return topk_pool_ref(ids, ds, k)
    kw.setdefault("interpret", jax.default_backend() != "tpu")
    out_i, out_d, _ = topk_merge_pallas(
        ids, jnp.where(ids < 0, jnp.inf, ds.astype(jnp.float32)),
        jnp.zeros(ids.shape, bool), k, nearest=True, **kw)
    return out_i, out_d
