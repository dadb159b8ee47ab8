"""Per-family sharding rules (logical names only — mesh-size agnostic).

LM      : Megatron TP on `model` (heads / d_ff / vocab / experts),
          DP on (`pod`, `data`); ZeRO-1 over DP for optimizer moments.
Recsys  : embedding tables row-sharded on `model`; dense MLPs DP
          (+ wide top-MLP hidden sharded on `model` for dlrm).
GNN     : params replicated (d_hidden=128); edges/triplets sharded over
          every mesh axis jointly (edge-partition scheme).
ANN     : handled in core.distributed (DB rows on `model`).

Rules are (regex on param path) -> PartitionSpec; first match wins.
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# ambient mesh for in-model sharding constraints
# ---------------------------------------------------------------------------

_ACTIVE_MESH: Optional[Mesh] = None


def set_active_mesh(mesh: Optional[Mesh]):
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def maybe_shard(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint when a mesh is active; no-op otherwise."""
    if _ACTIVE_MESH is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_ACTIVE_MESH, P(*spec)))


def shard_batch_seq(x: jax.Array, batch_dim: int = 0,
                    seq_dim: Optional[int] = None) -> jax.Array:
    """Constrain: batch dim over DP axes, optional seq dim over `model`
    (sequence parallelism — works for ANY head count, unlike head TP).
    Skips axes that don't divide; no-op without an active mesh."""
    mesh = _ACTIVE_MESH
    if mesh is None:
        return x
    dp = batch_axes(mesh)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    spec = [None] * x.ndim
    if x.shape[batch_dim] % dp_n == 0:
        spec[batch_dim] = dp
    if seq_dim is not None and x.shape[seq_dim] % mesh.shape["model"] == 0:
        spec[seq_dim] = "model"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def put_row_sharded(mesh: Mesh, x, *trailing) -> jax.Array:
    """``device_put`` with the leading dim on `model` — the ANN DB-row
    convention. ``trailing`` extends the spec for higher-rank arrays
    (usually ``None`` per extra dim). The one placement call behind both
    the sharded-index fit AND its rebuild-free reprune path, so a derived
    neighbors table always lands exactly where the original did."""
    return jax.device_put(x, NamedSharding(mesh, P("model", *trailing)))


def row_sharded_from_blocks(mesh: Mesh, blocks, *trailing) -> jax.Array:
    """Assemble a `model`-row-sharded global from per-shard blocks — the
    zero-host-concat placement path.

    ``blocks[i]`` is shard i's equal-shape slab (device or host). Each is
    ``device_put`` individually to every device in its `model` column
    (replicated across the other mesh axes) and the global is stitched
    with ``jax.make_array_from_single_device_arrays`` — at no point does a
    ``(shards * m, ...)`` host array exist, so peak host memory for a
    sharded fit is one shard, not N. The resulting array is
    indistinguishable from ``put_row_sharded`` of the concatenation."""
    s = mesh.shape["model"]
    if len(blocks) != s:
        raise ValueError(f"{len(blocks)} blocks for {s} `model` shards")
    shapes = {tuple(b.shape) for b in blocks}
    if len(shapes) > 1:
        raise ValueError(f"blocks must be equal-shape, got {shapes}")
    m = blocks[0].shape[0]
    shape = (s * m,) + tuple(blocks[0].shape[1:])
    sharding = NamedSharding(mesh, P("model", *trailing))
    axis = mesh.axis_names.index("model")
    shards = [jax.device_put(blocks[idx[axis]], dev)
              for idx, dev in np.ndenumerate(mesh.devices)]
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


def active_dp_axes() -> Optional[Tuple[str, ...]]:
    """DP axes of the ambient mesh (None when no mesh is active)."""
    if _ACTIVE_MESH is None:
        return None
    return batch_axes(_ACTIVE_MESH)


# ---------------------------------------------------------------------------
# rule machinery
# ---------------------------------------------------------------------------

Rule = Tuple[str, P]


def path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def spec_for(rules: List[Rule], path, leaf) -> P:
    s = path_str(path)
    for pat, spec in rules:
        if re.search(pat, s):
            # drop trailing axes that exceed leaf rank
            if len(spec) > leaf.ndim:
                spec = P(*spec[: leaf.ndim])
            # never shard an axis that is not divisible
            return spec
    return P()


def tree_shardings(mesh: Mesh, tree, rules: List[Rule]):
    def one(path, leaf):
        spec = spec_for(rules, path, leaf)
        # divisibility guard: replace non-divisible entries with None
        fixed = []
        for dim, ax in enumerate(tuple(spec) + (None,) * (leaf.ndim -
                                                          len(spec))):
            if ax is None:
                fixed.append(None)
                continue
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= mesh.shape[a]
            fixed.append(ax if leaf.shape[dim] % size == 0 else None)
        return NamedSharding(mesh, P(*fixed))
    return jax.tree_util.tree_map_with_path(one, tree)


# ---------------------------------------------------------------------------
# family rules
# ---------------------------------------------------------------------------


def lm_rules(mesh: Mesh) -> List[Rule]:
    # stacked layer params have a leading L axis -> specs shifted by one
    return [
        (r"embed$", P("model", None)),
        (r"lm_head$", P(None, "model")),
        # attention (stacked under layers/, unstacked under dense_layers/N/)
        (r"layers.*attn/w[qkv]$", P(None, None, "model")),
        (r"layers.*attn/wq_b$", P(None, None, "model")),
        (r"layers.*attn/wkv_b$", P(None, None, "model")),
        (r"layers.*attn/wo$", P(None, "model", None)),
        (r"layers.*attn/b[qkv]$", P(None, "model")),
        # MoE experts: EP on model
        (r"layers.*moe/w_(gate|up|down)$", P(None, "model", None, None)),
        (r"layers.*moe/shared/w_(gate|up)$", P(None, None, "model")),
        (r"layers.*moe/shared/w_down$", P(None, "model", None)),
        (r"layers.*moe/router$", P()),
        # dense FFN: TP on model
        (r"layers.*ffn/w_(gate|up)$", P(None, None, "model")),
        (r"layers.*ffn/w_down$", P(None, "model", None)),
        # dense_layers are unstacked (no leading L): shift left
        (r"dense_layers.*attn/w[qkv]$", P(None, "model")),
        (r"dense_layers.*attn/wo$", P("model", None)),
        (r"dense_layers.*(ffn|shared)/w_(gate|up)$", P(None, "model")),
        (r"dense_layers.*(ffn|shared)/w_down$", P("model", None)),
        (r"dense_layers.*moe/w_(gate|up|down)$", P("model", None, None)),
        (r".*", P()),
    ]


def recsys_rules(mesh: Mesh) -> List[Rule]:
    return [
        (r"(^|/)table$", P("model", None)),
        (r"top/layers/0/w$", P(None, "model")),
        (r"top/layers/1/w$", P("model", None)),
        (r".*", P()),
    ]


def gnn_rules(mesh: Mesh) -> List[Rule]:
    return [(r".*", P())]


def family_rules(family: str, mesh: Mesh) -> List[Rule]:
    return {"lm": lm_rules, "recsys": recsys_rules,
            "gnn": gnn_rules}[family](mesh)


# ---------------------------------------------------------------------------
# batch specs
# ---------------------------------------------------------------------------


def lm_batch_sharding(mesh: Mesh, batch):
    b = batch_axes(mesh)
    return jax.tree.map(
        lambda x: NamedSharding(mesh, P(b, *([None] * (x.ndim - 1)))), batch)


def kv_cache_sharding(mesh: Mesh, cache, cfg):
    """Cache (L, B, S, ...) : batch on data axes; GQA kv-head dim on model
    when divisible, else the sequence dim."""
    b = batch_axes(mesh)

    def one(x):
        if x.ndim == 5:                        # (L, B, S, KV, hd)
            kv = x.shape[3]
            if kv % mesh.shape["model"] == 0:
                return NamedSharding(mesh, P(None, b, None, "model", None))
            return NamedSharding(mesh, P(None, b, "model", None, None))
        if x.ndim == 4:                        # (L, B, S, r) MLA latent
            return NamedSharding(mesh, P(None, b, "model", None))
        return NamedSharding(mesh, P(b))       # lengths (B,)
    return jax.tree.map(one, cache)


def gnn_batch_sharding(mesh: Mesh, graph):
    """Edges/triplets sharded across ALL axes; nodes replicated."""
    every = tuple(mesh.axis_names)

    def one(path, x):
        name = path_str(path)
        if re.search(r"src|dst|edge_mask|t_kj|t_ji", name):
            ax = every if x.shape[0] % _axes_size(mesh, every) == 0 else None
            return NamedSharding(mesh, P(ax, *([None] * (x.ndim - 1))))
        return NamedSharding(mesh, P(*([None] * x.ndim)))
    return jax.tree_util.tree_map_with_path(one, graph)


def recsys_batch_sharding(mesh: Mesh, batch):
    b = batch_axes(mesh)

    def one(x):
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        ok = x.shape[0] % _axes_size(mesh, b) == 0
        return NamedSharding(mesh, P(b if ok else None,
                                     *([None] * (x.ndim - 1))))
    return jax.tree.map(one, batch)


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def zero1_shardings(mesh: Mesh, param_shardings, opt_state):
    """ZeRO-1: shard optimizer moments' leading dim over DP axes when the
    param itself leaves that dim unsharded and it divides evenly."""
    b = batch_axes(mesh)
    dp = _axes_size(mesh, b)

    def one(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] % dp == 0:
            return NamedSharding(mesh, P(b, *([None] * (x.ndim - 1))))
        return NamedSharding(mesh, P())
    # only the m/v moments (large); step stays replicated
    return jax.tree.map(
        lambda x: one(x) if hasattr(x, "ndim") and x.ndim > 0
        else NamedSharding(mesh, P()), opt_state)
