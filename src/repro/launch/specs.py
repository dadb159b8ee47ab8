"""Dry-run cell builders: for every (arch × shape × mesh) return the jitted
step (with explicit in/out shardings + donation) and ShapeDtypeStruct args —
`.lower(*args).compile()` is the multi-pod proof, no allocation ever happens.

input_specs() follows the system contract: training cells lower train_step,
decode cells lower serve_step (one token against a full KV cache), serve /
retrieval cells lower the scoring step.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.core.distributed import (
    ShardedIndexArrays, input_specs_for_search, make_search_step,
    make_sharded_l2_topk,
)
from repro.distributed import sharding as SH
from repro.models import dimenet, recsys, transformer
from repro.models.recsys_common import make_sharded_lookup
from repro.optim import adamw, mixed_optimizer
from repro.serve.serve_step import recsys_retrieval_step, recsys_score_step
from repro.train.train_step import loss_fn_for, make_train_step

SDS = jax.ShapeDtypeStruct


@dataclass
class Cell:
    arch: str
    shape: str
    fn: Any                     # jitted, shardings attached
    args: tuple                 # ShapeDtypeStructs
    kind: str
    model_flops: float = 0.0
    notes: str = ""


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def _dp(mesh) -> Tuple[str, ...]:
    return SH.batch_axes(mesh)


def _dp_size(mesh) -> int:
    n = 1
    for a in _dp(mesh):
        n *= mesh.shape[a]
    return n


def _eval_shape(fn, *a, **k):
    return jax.eval_shape(fn, *a, **k)


def _add_dp(mesh, spec_tuple, shape, dp, dp_n):
    """Add the DP axes to the first unsharded, divisible dim (ZeRO/FSDP).
    No-op if any DP axis is already used (a mesh axis may appear once)."""
    spec = list(spec_tuple) + [None] * (len(shape) - len(spec_tuple))
    used = set()
    for e in spec:
        for a in (e if isinstance(e, tuple) else (e,)):
            used.add(a)
    if any(a in used for a in dp):
        return tuple(spec)
    for d in range(len(shape)):
        if spec[d] is None and shape[d] % dp_n == 0 and shape[d] >= dp_n:
            spec[d] = dp
            break
    return tuple(spec)


def _opt_shardings(mesh, param_sh, opt_shape):
    """AdamW moments: inherit the param's spec + ZeRO-1 over DP on the first
    divisible unsharded dim (not just dim 0 — expert stacks have L=59)."""
    dp = _dp(mesh)
    dp_n = _dp_size(mesh)

    def moment(ps, leaf):
        spec = _add_dp(mesh, tuple(ps.spec), leaf.shape, dp, dp_n)
        return NamedSharding(mesh, P(*spec))

    return {
        "m": jax.tree.map(moment, param_sh, opt_shape["m"]),
        "v": jax.tree.map(moment, param_sh, opt_shape["v"]),
        "step": NamedSharding(mesh, P()),
    }


def _fsdp_shardings(mesh, param_sh, params_shape,
                    min_bytes: int = 32 << 20):
    """P7: also shard big params over DP (XLA re-gathers per scanned layer).
    Keeps small leaves (norms, biases) replicated."""
    dp = _dp(mesh)
    dp_n = _dp_size(mesh)

    def one(ps, leaf):
        size = leaf.size * leaf.dtype.itemsize
        if size < min_bytes:
            return ps
        spec = _add_dp(mesh, tuple(ps.spec), leaf.shape, dp, dp_n)
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, param_sh, params_shape)


# ===========================================================================
# LM cells
# ===========================================================================


def _lm_cell(spec, shape: ShapeConfig, mesh: Mesh) -> Cell:
    from repro.analysis.roofline import lm_model_flops
    cfg = spec.config
    dp = _dp(mesh)
    dp_n = _dp_size(mesh)
    params_shape = _eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    param_sh = SH.tree_shardings(mesh, params_shape, SH.lm_rules(mesh))
    mf = lm_model_flops(cfg, shape, shape.kind)

    if shape.kind == "train":
        from repro import flags
        if flags.LM_FSDP:
            param_sh = _fsdp_shardings(mesh, param_sh, params_shape)
        opt = adamw(3e-4)
        opt_shape = _eval_shape(opt.init, params_shape)
        opt_sh = _opt_shardings(mesh, param_sh, opt_shape)
        per_dev = shape.global_batch // dp_n
        micro = per_dev if cfg.d_model >= 4096 else max(1, per_dev // 4)
        step = make_train_step(
            loss_fn_for("lm", cfg), opt, microbatches=micro,
            grad_shardings=param_sh if flags.GRAD_SHARD_CONSTRAINTS
            else None)
        batch_sh = {"tokens": _ns(mesh, dp, None),
                    "labels": _ns(mesh, dp, None)}
        fn = jax.jit(step, in_shardings=(param_sh, opt_sh, batch_sh),
                     out_shardings=(param_sh, opt_sh, None),
                     donate_argnums=(0, 1))
        b = {"tokens": SDS((shape.global_batch, shape.seq_len), jnp.int32),
             "labels": SDS((shape.global_batch, shape.seq_len), jnp.int32)}
        return Cell(spec.arch_id, shape.name, fn,
                    (params_shape, opt_shape, b), "train", mf,
                    notes=f"microbatches={micro}, ZeRO-1 moments")

    if shape.kind == "prefill":
        def step(params, tokens):
            logits, cache = transformer.prefill(params, cfg, tokens)
            return logits[:, -1], cache
        cache_shape = _eval_shape(
            lambda: transformer.init_cache(cfg, shape.global_batch,
                                           shape.seq_len))
        cache_sh = SH.kv_cache_sharding(mesh, cache_shape, cfg)
        fn = jax.jit(step,
                     in_shardings=(param_sh, _ns(mesh, dp, None)),
                     out_shardings=(_ns(mesh, dp, None), cache_sh))
        t = SDS((shape.global_batch, shape.seq_len), jnp.int32)
        return Cell(spec.arch_id, shape.name, fn, (params_shape, t),
                    "prefill", mf, notes="chunked (flash) attention")

    # decode: one token against a seq_len KV cache
    def step(params, token, cache, pos):
        return transformer.decode_step(params, cfg, token, cache, pos)
    cache_shape = _eval_shape(
        lambda: transformer.init_cache(cfg, shape.global_batch,
                                       shape.seq_len))
    cache_sh = SH.kv_cache_sharding(mesh, cache_shape, cfg)
    fn = jax.jit(step,
                 in_shardings=(param_sh, _ns(mesh, dp), cache_sh,
                               _ns(mesh, dp)),
                 out_shardings=(_ns(mesh, dp, None), cache_sh),
                 donate_argnums=(2,))
    tok = SDS((shape.global_batch,), jnp.int32)
    pos = SDS((shape.global_batch,), jnp.int32)
    notes = "absorbed-MLA latent cache" if cfg.use_mla else \
        "KV cache seq-sharded on model"
    return Cell(spec.arch_id, shape.name, fn,
                (params_shape, tok, cache_shape, pos), "decode", mf,
                notes=notes)


# ===========================================================================
# GNN cells
# ===========================================================================


def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _gnn_graph_specs(shape: ShapeConfig, mesh: Mesh) -> Dict[str, Any]:
    all_ax = tuple(mesh.axis_names)
    n_sh = int(np.prod([mesh.shape[a] for a in all_ax]))
    if shape.name == "molecule":
        n_nodes = shape.n_nodes * shape.n_graphs
        n_edges = _pad_to(shape.n_edges * shape.n_graphs, n_sh)
        n_tri = _pad_to(shape.n_triplets * shape.n_graphs, n_sh)
        n_graphs = shape.n_graphs
    else:
        n_nodes = shape.n_nodes
        n_edges = _pad_to(shape.n_edges, n_sh)
        n_tri = _pad_to(shape.n_triplets, n_sh)
        n_graphs = 1
    g = {
        "pos": SDS((n_nodes, 3), jnp.float32),
        "src": SDS((n_edges,), jnp.int32),
        "dst": SDS((n_edges,), jnp.int32),
        "edge_mask": SDS((n_edges,), jnp.bool_),
        "t_kj": SDS((n_tri,), jnp.int32),
        "t_ji": SDS((n_tri,), jnp.int32),
        "node_mask": SDS((n_nodes,), jnp.bool_),
        "graph_id": SDS((n_nodes,), jnp.int32),
    }
    if shape.d_feat:
        g["x"] = SDS((n_nodes, shape.d_feat), jnp.float32)
    else:
        g["z"] = SDS((n_nodes,), jnp.int32)
    if shape.name == "molecule":
        g["y_graph"] = SDS((n_graphs,), jnp.float32)
    else:
        g["y_node"] = SDS((n_nodes,), jnp.float32)
    return g


def make_gnn_loss(cfg, mesh: Mesh):
    """Edge-partition distributed loss: edges/triplets sharded over every
    axis, nodes replicated, one psum of node partials. Triplet indices are
    shard-local by construction (data/graph_sampler.build_triplets_sharded).
    """
    all_ax = tuple(mesh.axis_names)
    edge_keys = ("src", "dst", "edge_mask", "t_kj", "t_ji")

    def local_loss(params, graph):
        reduce = lambda x: jax.lax.psum(x, all_ax)
        loss, _ = dimenet.loss_fn(params, cfg, graph, node_reduce=reduce)
        return loss

    def in_spec_for(key):
        return P(all_ax) if key in edge_keys else P()

    def sharded_loss(params, graph):
        keys = sorted(graph.keys())
        vals = [graph[k] for k in keys]

        def wrapper(params, *vals):
            g = dict(zip(keys, vals))
            return local_loss(params, g)

        mapped = jax.shard_map(
            wrapper, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),
                      *[in_spec_for(k) for k in keys]),
            out_specs=P())
        return mapped(params, *vals), {}

    return sharded_loss


def _gnn_cell(spec, shape: ShapeConfig, mesh: Mesh) -> Cell:
    cfg = spec.config
    d_feat = shape.d_feat
    params_shape = _eval_shape(
        lambda: dimenet.init_params(jax.random.PRNGKey(0), cfg,
                                    d_feat=d_feat))
    param_sh = SH.tree_shardings(mesh, params_shape, SH.gnn_rules(mesh))
    loss = make_gnn_loss(cfg, mesh)
    opt = adamw(1e-3)
    opt_shape = _eval_shape(opt.init, params_shape)
    opt_sh = _opt_shardings(mesh, param_sh, opt_shape)
    step = make_train_step(lambda p, b: loss(p, b), opt)
    g = _gnn_graph_specs(shape, mesh)
    g_sh = SH.gnn_batch_sharding(mesh, g)
    fn = jax.jit(step, in_shardings=(param_sh, opt_sh, g_sh),
                 out_shardings=(param_sh, opt_sh, None),
                 donate_argnums=(0, 1))
    # model flops ~ triplet bilinear + edge MLPs (analytic, f32)
    h, nb = cfg.d_hidden, cfg.n_bilinear
    tri_flops = 2.0 * g["t_kj"].shape[0] * (nb * h * h + nb * h)
    edge_flops = 2.0 * g["src"].shape[0] * (6 * h * h)
    mf = 3.0 * cfg.n_blocks * (tri_flops + edge_flops)   # fwd+bwd
    return Cell(spec.arch_id, shape.name, fn, (params_shape, opt_shape, g),
                "train", mf,
                notes="edge-partition shard_map; shard-local triplets")


# ===========================================================================
# Recsys cells
# ===========================================================================


def _recsys_batch_specs(cfg, batch: int) -> Dict[str, Any]:
    multi_hot = cfg.multi_hot or (1,) * cfg.n_sparse
    b: Dict[str, Any] = {
        "sparse_ids": [SDS((batch, m), jnp.int32) for m in multi_hot],
        "label": SDS((batch,), jnp.float32),
    }
    if cfg.n_dense:
        b["dense"] = SDS((batch, cfg.n_dense), jnp.float32)
    if cfg.seq_len and cfg.interaction in ("self-attn-seq", "target-attn"):
        b["history"] = SDS((batch, cfg.seq_len), jnp.int32)
        b["history_len"] = SDS((batch,), jnp.int32)
        b["target"] = SDS((batch,), jnp.int32)
    return b


def _mixed_opt_shardings(mesh, param_sh, opt_shape):
    def one(ps, leaf):
        if isinstance(leaf, dict):
            return leaf
        return None
    # acc rows follow the table sharding; dense moments replicated
    def leaf_sh(path, leaf):
        s = SH.path_str(path)
        if "/acc" in s or s.endswith("acc"):
            return NamedSharding(mesh, P("model"))
        return NamedSharding(mesh, P())
    return {
        "leaves": jax.tree_util.tree_map_with_path(
            leaf_sh, opt_shape["leaves"]),
        "step": NamedSharding(mesh, P()),
    }


def _recsys_cell(spec, shape: ShapeConfig, mesh: Mesh) -> Cell:
    cfg = spec.config
    dp = _dp(mesh)
    from repro.models.recsys_common import padded_rows
    fam = recsys.family_of(cfg)
    lookup = make_sharded_lookup(mesh, padded_rows(cfg.table_vocabs))
    params_shape = _eval_shape(
        lambda: recsys.INIT[fam](jax.random.PRNGKey(0), cfg))
    param_sh = SH.tree_shardings(mesh, params_shape,
                                 SH.recsys_rules(mesh))
    # analytic flops: lookups + mlps (order of magnitude, fwd only)
    d = cfg.embed_dim

    if shape.kind == "train":
        opt = mixed_optimizer(1e-3)
        opt_shape = _eval_shape(opt.init, params_shape)
        opt_sh = _mixed_opt_shardings(mesh, param_sh, opt_shape)
        loss = loss_fn_for("recsys", cfg, lookup_fn=lookup)
        step = make_train_step(loss, opt)
        b = _recsys_batch_specs(cfg, shape.batch)
        b_sh = SH.recsys_batch_sharding(mesh, b)
        fn = jax.jit(step, in_shardings=(param_sh, opt_sh, b_sh),
                     out_shardings=(param_sh, opt_sh, None),
                     donate_argnums=(0, 1))
        mf = 6.0 * shape.batch * (cfg.n_sparse + 10) * d * d
        return Cell(spec.arch_id, shape.name, fn,
                    (params_shape, opt_shape, b), "train", mf,
                    notes="row-sharded tables (shard_map psum) + "
                          "rowwise-adagrad")

    if shape.kind == "serve":
        step = recsys_score_step(cfg, lookup_fn=lookup)
        b = _recsys_batch_specs(cfg, shape.batch)
        b_sh = SH.recsys_batch_sharding(mesh, b)
        fn = jax.jit(step, in_shardings=(param_sh, b_sh),
                     out_shardings=_ns(mesh, dp))
        mf = 2.0 * shape.batch * (cfg.n_sparse + 10) * d * d
        return Cell(spec.arch_id, shape.name, fn, (params_shape, b),
                    "serve", mf)

    # retrieval_cand: 1 query x 1M candidates
    step = recsys_retrieval_step(cfg, k=10, lookup_fn=lookup)
    b = _recsys_batch_specs(cfg, shape.batch)
    b_sh = SH.recsys_batch_sharding(mesh, b)
    cand = SDS((shape.n_candidates,), jnp.int32)
    fn = jax.jit(step, in_shardings=(param_sh, b_sh, _ns(mesh, dp)),
                 out_shardings=(None, None))
    mf = 2.0 * shape.n_candidates * d * d * 4
    return Cell(spec.arch_id, shape.name, fn, (params_shape, b, cand),
                "retrieval", mf)


# ===========================================================================
# ANN cells (the paper's own serving workload)
# ===========================================================================


def _ann_cell(spec, shape: ShapeConfig, mesh: Mesh) -> Cell:
    cfg = spec.config
    n_shards = mesh.shape["model"]
    if shape.kind == "retrieval":
        step = make_search_step(mesh, ef=cfg.ef_search, k=cfg.k,
                                mode="fori")
        sp = input_specs_for_search(cfg, shape.batch, shape.n_candidates,
                                    n_shards)
        arr = sp["arrays"]
        arr_sh = ShardedIndexArrays(
            base=_ns(mesh, "model", None),
            neighbors=_ns(mesh, "model", None),
            global_ids=_ns(mesh, "model"),
            centroids=_ns(mesh, "model", None),
            members=_ns(mesh, "model"),
            pca_mean=_ns(mesh), pca_comp=_ns(mesh, None, None),
            base_norms=_ns(mesh, "model"))
        dp = _dp(mesh)
        fn = jax.jit(step.__wrapped__,
                     in_shardings=(_ns(mesh, dp, None), arr_sh),
                     out_shardings=(_ns(mesh, dp, None),
                                    _ns(mesh, dp, None)))
        # beam: max_iters expansions x R gathered rows x D dims per query
        mf = (2.0 * shape.batch * 4 * cfg.ef_search * cfg.graph_degree
              * cfg.pca_dim)
        return Cell(spec.arch_id, shape.name, fn,
                    (sp["queries"], arr), "retrieval", mf,
                    notes=f"{n_shards} sub-graphs, fixed-beam fori, "
                          f"ef={cfg.ef_search}")
    # build_knn: the sharded brute-force distance pass of the index build
    fn_raw = make_sharded_l2_topk(mesh, k=cfg.build_knn_k)
    q = SDS((shape.batch, cfg.pca_dim), jnp.float32)
    db = SDS((shape.n_candidates, cfg.pca_dim), jnp.float32)
    offs = SDS((n_shards,), jnp.int32)
    dp = _dp(mesh)
    fn = jax.jit(fn_raw.__wrapped__,
                 in_shardings=(_ns(mesh, dp, None),
                               _ns(mesh, "model", None),
                               _ns(mesh, "model")),
                 out_shardings=(_ns(mesh, dp, None), _ns(mesh, dp, None)))
    mf = 2.0 * shape.batch * shape.n_candidates * cfg.pca_dim
    return Cell(spec.arch_id, shape.name, fn, (q, db, offs), "build", mf)


# ===========================================================================
# dispatch
# ===========================================================================


def build_cell(arch_id: str, shape_name: str, mesh: Mesh) -> Cell:
    SH.set_active_mesh(mesh)     # enables in-model sharding constraints
    spec = get_arch(arch_id)
    shape = spec.shape(shape_name)
    reason = spec.skip_reason(shape_name)
    if reason:
        raise ValueError(f"cell skipped: {reason}")
    if spec.family == "lm":
        return _lm_cell(spec, shape, mesh)
    if spec.family == "gnn":
        return _gnn_cell(spec, shape, mesh)
    if spec.family == "recsys":
        return _recsys_cell(spec, shape, mesh)
    if spec.family == "ann":
        return _ann_cell(spec, shape, mesh)
    raise KeyError(spec.family)
