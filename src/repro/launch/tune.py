"""Black-box tuning launcher — the paper's §3.2 workflow as a CLI.

    PYTHONPATH=src python -m repro.launch.tune --n 2000 --dim 64 \
        --trials 15 --mode multi

Pass ``--spec`` to tune a factory-built off-the-shelf index instead of the
paper's full pipeline: the space then comes from the index's own
``search_params_space()`` and the same Study drives it, whatever the family:

    PYTHONPATH=src python -m repro.launch.tune --spec "IVF128,Flat" --trials 10

Add ``--shards`` to a graph-family spec to tune a *sharded* deployment's
(graph_degree, alpha, ef_search): every shard builds once at the structural
maximum and all degree/alpha trials are served by per-shard reprune —
zero rebuilds, asserted by the structural-build counter in the log:

    PYTHONPATH=src python -m repro.launch.tune --spec "NSG16" --shards 4

``--shards`` WITHOUT ``--spec`` shards the paper's full pipeline itself:
an SPMD ``ShardedIndex`` when the backend has >= shards devices, the
host-offload ``StreamedShardedIndex`` tier otherwise (shards stream
through the device one at a time — N is bounded by host RAM, not HBM).
``--bench-build-out BENCH_build.json`` appends the per-stage build
timings (knn / pools / prune / finish / total, summed over shards) as a
``stage="sharded_build"`` point — how the >= 1M build-scaling points are
produced:

    PYTHONPATH=src python -m repro.launch.tune --n 1000000 --dim 16 \
        --shards 8 --bench-build-out BENCH_build.json --trials 3
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core import FlatIndex, IndexParams
from repro.core.tuning import (
    AnnObjective, SearchParamsObjective, ShardedRepruneObjective, Study,
    TPESampler, default_space,
)
from repro.data import clustered_vectors, queries_like
from repro.launch.compile_cache import enable_compile_cache


def merge_bench_point(path: str, point: dict) -> None:
    """Append one point to ``BENCH_build.json``-style artifacts in place.

    Existing points for the same (stage, n, shards, path) are replaced —
    re-running the bench updates its own row instead of accumulating
    duplicates — and a missing/invalid file starts a fresh document.
    """
    doc = {"backend": jax.default_backend(), "points": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            pass
    keyof = lambda p: (p.get("stage"), p.get("n"), p.get("shards"),
                       p.get("path"))
    doc["points"] = [p for p in doc.get("points", [])
                     if keyof(p) != keyof(point)] + [point]
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--trials", type=int, default=12)
    ap.add_argument("--mode", choices=["single", "multi"], default="multi")
    ap.add_argument("--recall-floor", type=float, default=0.9)
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--spec", default=None,
                    help="factory spec: tune SearchParams for this index "
                         "instead of the pipeline's build knobs")
    ap.add_argument("--shards", type=int, default=0,
                    help="with --spec on a graph family: shard the spec "
                         "and sweep (graph_degree, alpha, ef_search) via "
                         "per-shard reprune — one structural build per "
                         "shard, everything else derived")
    ap.add_argument("--knn-backend", default="auto",
                    choices=["exact", "nndescent", "auto"],
                    help="build-time kNN-graph backend (core.build): exact "
                         "O(N^2) pass, NN-Descent refinement, or auto by N")
    ap.add_argument("--finish-backend", default="auto",
                    choices=["host", "device", "auto"],
                    help="NSG finishing pass (build.finish): device "
                         "scatter-min interconnect + batched repair, or "
                         "the host numpy parity path (auto = device)")
    ap.add_argument("--max-degree", type=int, default=16,
                    help="structural graph-degree ceiling: the single real "
                         "build per structure happens here; degree/alpha "
                         "trials reprune down from it")
    ap.add_argument("--dist-backend", default=None,
                    choices=["f32", "pq", "int8"],
                    help="quantized-traversal serving (core.quant): with "
                         "--spec, a per-shard/index build override; without "
                         "it, adds dist_backend + rerank to the tuned space "
                         "(codes encode once per structural build)")
    ap.add_argument("--rerank", type=int, default=None,
                    help="exact-rerank depth of the quantized beam tail "
                         "(SearchParams.rerank / IndexParams.rerank)")
    ap.add_argument("--hop-backend", default=None,
                    choices=["staged", "fused", "auto"],
                    help="beam-hop serving backend (core.beam_search): "
                         "staged gather/distance/merge ops, or the fused "
                         "kernels/beam_hop launch; auto = fused on TPU. "
                         "Without --spec the knob is tuned (it is in "
                         "default_space); this pins it instead")
    ap.add_argument("--patience", type=int, default=None,
                    help="adaptive early-termination hops (core.beam_search"
                         " straggler control): a lane stops after this many "
                         "hops without top-k progress > --eps; 0 = stock "
                         "convergence. Without --spec the knob is tuned "
                         "(it is in default_space); this pins it instead")
    ap.add_argument("--eps", type=float, default=None,
                    help="top-k improvement threshold that counts as "
                         "progress for --patience (squared-L2 units)")
    ap.add_argument("--offload", action="store_true",
                    help="with --shards (no --spec): force the host-offload "
                         "streamed tier even when the mesh has enough "
                         "devices for the SPMD path")
    ap.add_argument("--bench-build-out", default=None,
                    help="with --shards (no --spec): merge a "
                         "stage='sharded_build' per-stage timing point "
                         "into this BENCH_build.json-style file")
    ap.add_argument("--pca-dim", type=int, default=None,
                    help="pipeline PCA target dim (default: --dim, i.e. "
                         "projection off)")
    args = ap.parse_args()
    enable_compile_cache()

    key = jax.random.PRNGKey(0)
    data = clustered_vectors(key, args.n, args.dim, n_clusters=32)
    queries = queries_like(jax.random.PRNGKey(1), data, args.queries)
    if args.spec and args.shards > 1:
        from repro.core.distributed import ShardedFactoryIndex
        from repro.core.pipeline import structural_build_count
        b0 = structural_build_count()
        idx = ShardedFactoryIndex(args.spec, n_shards=args.shards,
                                  knn_backend=args.knn_backend,
                                  finish_backend=args.finish_backend,
                                  dist_backend=args.dist_backend,
                                  rerank=args.rerank,
                                  hop_backend=args.hop_backend,
                                  patience=args.patience,
                                  eps=args.eps).fit(
            data, key=key)
        obj = ShardedRepruneObjective(idx, data, queries, k=10,
                                      recall_floor=args.recall_floor,
                                      qps_repeats=3)
        space = obj.space
    elif args.spec:
        index = args.spec
        if (args.dist_backend is not None or args.rerank is not None
                or args.hop_backend is not None
                or args.patience is not None or args.eps is not None):
            from repro.core.index_api import build_index
            index = build_index(args.spec, data, key=key,
                                knn_backend=args.knn_backend,
                                finish_backend=args.finish_backend,
                                dist_backend=args.dist_backend,
                                rerank=args.rerank,
                                hop_backend=args.hop_backend,
                                patience=args.patience,
                                eps=args.eps)
        obj = SearchParamsObjective(index, data, queries, k=10,
                                    recall_floor=args.recall_floor,
                                    qps_repeats=3, key=key)
        space = obj.space
    elif args.shards > 1:
        # paper pipeline, sharded: SPMD mesh when the backend has enough
        # devices, host-offload streaming otherwise; either way ONE
        # structural build per shard and reprune-derived trials
        from jax.sharding import Mesh
        from repro.core.distributed import (
            ShardedIndex, StreamedShardedIndex,
        )
        from repro.core.pipeline import structural_build_count
        b0 = structural_build_count()
        p = IndexParams(
            pca_dim=args.pca_dim or args.dim,
            graph_degree=args.max_degree, build_knn_k=args.max_degree,
            build_candidates=2 * args.max_degree, ef_search=64,
            knn_backend=args.knn_backend,
            finish_backend=args.finish_backend)
        devs = jax.devices()
        t0 = time.perf_counter()
        if not args.offload and len(devs) >= args.shards:
            mesh = Mesh(np.array(devs[:args.shards]).reshape(
                1, args.shards), ("data", "model"))
            idx = ShardedIndex(p, mesh).fit(data, key=key)
            path_name = "spmd"
        else:
            idx = StreamedShardedIndex(p, n_shards=args.shards).fit(
                data, key=key)
            path_name = "streamed"
        build_seconds = time.perf_counter() - t0
        stats = idx.shard_stats
        agg = {f: round(sum(s[f] for s in stats), 3)
               for f in ("knn_seconds", "pools_seconds", "prune_seconds",
                         "finish_seconds")}
        print(f"sharded build ({path_name}): {args.shards} shards, "
              f"{build_seconds:.1f}s total "
              + " ".join(f"{k_}={v}" for k_, v in agg.items()))
        if args.bench_build_out:
            merge_bench_point(args.bench_build_out, {
                "n": args.n, "dim": args.dim, "stage": "sharded_build",
                "shards": args.shards, "path": path_name,
                "degree": args.max_degree,
                "knn_backend": args.knn_backend,
                "seconds": round(build_seconds, 3), **agg,
            })
            print(f"merged sharded_build point into "
                  f"{args.bench_build_out}")
        obj = ShardedRepruneObjective(idx, data, queries, k=10,
                                      recall_floor=args.recall_floor,
                                      qps_repeats=3)
        space = obj.space
    else:
        quantized = (args.dist_backend is not None
                     or args.rerank is not None)
        base = IndexParams(pca_dim=args.dim, graph_degree=args.max_degree,
                           build_knn_k=args.max_degree,
                           build_candidates=2 * args.max_degree,
                           ef_search=64, knn_backend=args.knn_backend,
                           finish_backend=args.finish_backend,
                           dist_backend=args.dist_backend or "f32",
                           rerank=args.rerank if args.rerank is not None
                           else 64,
                           hop_backend=args.hop_backend or "auto",
                           patience=args.patience or 0,
                           eps=args.eps or 0.0)
        obj = AnnObjective(data, queries, k=10, base_params=base,
                           recall_floor=args.recall_floor, qps_repeats=3)
        space = default_space(args.dim, args.n,
                              max_degree=args.max_degree,
                              quantized=quantized)

    if args.mode == "single":
        study = Study(space, TPESampler(seed=0, n_startup=5))
        study.optimize(obj.single_objective, n_trials=args.trials,
                       timeout=args.timeout)
        best = study.best_trial
        results = [best]
    else:
        study = Study(space, TPESampler(seed=0, n_startup=5),
                      n_objectives=2)
        study.optimize(obj.multi_objective, n_trials=args.trials,
                       timeout=args.timeout)
        results = study.pareto_front()

    print(f"\n{'params':60s} recall   qps")
    for t in sorted(results, key=lambda t: -t.values[0]):
        r = t.user_attrs["result"]
        print(f"{str(t.params):60s} {r.recall:.4f}  {r.qps:.0f}")

    # build-cache efficacy: what each trial actually paid for its graph
    print(f"\n-- build log ({len(obj.eval_log)} evals) --")
    for i, (params, r) in enumerate(obj.eval_log):
        if not r.cached_build:
            tag = "full-build"
        elif getattr(r, "repruned", False):
            tag = "reprune"
        else:
            tag = "cached"
        print(f"trial {i:02d} {tag:10s} build={r.build_seconds:6.2f}s "
              f"recall={r.recall:.4f} qps={r.qps:.0f} {params}")
    full = sum(1 for _, r in obj.eval_log if not r.cached_build)
    repr_ = sum(1 for _, r in obj.eval_log
                if r.cached_build and getattr(r, "repruned", False))
    cached = len(obj.eval_log) - full - repr_
    print(f"{full} structural builds, {repr_} reprune derivations, "
          f"{cached} pure cache hits (the §5.3 rebuild cost fix)")
    if hasattr(obj, "grid_hits"):
        fam = getattr(obj, "family_prunes", getattr(obj, "reprunes", 0))
        print(f"reprune grid: {fam} family/derivation passes, "
              f"{obj.grid_hits} pure grid lookups")
    if args.shards > 1:
        from repro.core.pipeline import structural_build_count
        built = structural_build_count() - b0
        print(f"sharded sweep: {built} structural builds for "
              f"{args.shards} shards "
              f"({'OK — one per shard' if built == args.shards else 'REBUILD LEAK'})")
    if args.out:
        with open(args.out, "w") as f:
            json.dump([{"params": t.params, "values": t.values}
                       for t in results], f, indent=1)


if __name__ == "__main__":
    main()
