"""Recsys substrate: embedding tables (concatenated + optionally row-sharded)
and interaction helpers.

JAX has no native EmbeddingBag or CSR sparse — lookups are built from
``jnp.take`` (+ ``segment_sum``-equivalent masked reduces), exactly as the
assignment mandates; the Pallas `embedding_bag` kernel is the TPU hot-path
variant of the same op.

All tables of a model concatenate into ONE (sum_V, D) matrix with static row
offsets — balanced row-wise sharding on the `model` axis regardless of
per-table skew (Criteo's tables span 3 rows .. 40M rows).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.embedding_bag import embedding_bag

Params = Dict[str, Any]


def table_offsets(vocabs: Sequence[int]) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(vocabs)])[:-1].astype(np.int64)


def padded_rows(vocabs: Sequence[int], multiple: int = 512) -> int:
    """Concatenated row count padded so any mesh axis (<=512) divides it."""
    total = int(sum(vocabs))
    return -(-total // multiple) * multiple


def init_tables(key, vocabs: Sequence[int], dim: int,
                dtype=jnp.float32) -> jax.Array:
    scale = dim ** -0.5
    return (jax.random.normal(key, (padded_rows(vocabs), dim))
            * scale).astype(dtype)


def globalize_ids(ids_per_table: List[jax.Array],
                  offsets: np.ndarray) -> jax.Array:
    """[(B, L_t)] -> (B, sum L_t) ids into the concatenated table."""
    return jnp.concatenate(
        [ids + int(offsets[t]) for t, ids in enumerate(ids_per_table)],
        axis=1)


def lookup(table: jax.Array, global_ids: jax.Array,
           backend: str = "jnp") -> jax.Array:
    """(B, T) -> (B, T, D) single-hot gather."""
    return table[global_ids]


def bag_lookup(table: jax.Array, ids: jax.Array, combiner: str = "mean",
               backend: str = "jnp") -> jax.Array:
    """(B, L) multi-hot (-1 padded) -> (B, D)."""
    return embedding_bag(table, ids, None, combiner, backend=backend)


def make_sharded_lookup(mesh: Mesh, total_rows: int):
    """Row-sharded embedding lookup: local masked take + psum('model').

    table sharded P('model', None); FLAT ids sharded on the batch axes when
    divisible (replicated fallback for tiny query batches). Returns
    fn(table, flat_ids (N,)) -> (N, D).
    """
    batch = tuple(a for a in mesh.axis_names if a != "model")
    n_shards = mesh.shape["model"]
    dp = 1
    for a in batch:
        dp *= mesh.shape[a]
    rows_local = -(-total_rows // n_shards)

    def local(table_local, ids, shard_idx):
        lo = shard_idx[0] * rows_local
        loc = ids - lo
        mask = (loc >= 0) & (loc < table_local.shape[0])
        safe = jnp.clip(loc, 0, table_local.shape[0] - 1)
        rows = table_local[safe]
        rows = jnp.where(mask[..., None], rows, 0)
        return jax.lax.psum(rows, "model")

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model", None), P(batch), P("model")),
        out_specs=P(batch, None))
    mapped_rep = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model", None), P(), P("model")),
        out_specs=P(None, None))

    def fn(table, flat_ids):
        shard_idx = jnp.arange(n_shards, dtype=jnp.int32)
        m = mapped if flat_ids.shape[0] % dp == 0 else mapped_rep
        return m(table, flat_ids, shard_idx)

    return fn


# ---------------------------------------------------------------- interact
def dot_interaction(vectors: jax.Array) -> jax.Array:
    """DLRM dot-interaction: (B, F, D) -> (B, F*(F-1)/2) pairwise dots."""
    b, f, d = vectors.shape
    z = jnp.einsum("bfd,bgd->bfg", vectors, vectors)
    iu = jnp.triu_indices(f, k=1)
    return z[:, iu[0], iu[1]]


def sampled_softmax_loss(user_vecs: jax.Array, item_vecs: jax.Array,
                         log_q: Optional[jax.Array] = None,
                         temperature: float = 0.05) -> jax.Array:
    """In-batch softmax with logQ correction (two-tower retrieval)."""
    logits = (user_vecs @ item_vecs.T) / temperature
    if log_q is not None:
        logits = logits - log_q[None, :]
    labels = jnp.arange(logits.shape[0])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def bce_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logits = logits.reshape(labels.shape)
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))
