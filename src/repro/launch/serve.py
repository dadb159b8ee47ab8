"""Serving launcher: one batched request cycle per family.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b
    PYTHONPATH=src python -m repro.launch.serve --arch two-tower-retrieval
    PYTHONPATH=src python -m repro.launch.serve --arch ann-laion
    PYTHONPATH=src python -m repro.launch.serve --arch ann-laion \
        --n 4000 --spec "PCA32,NSG16,EP16" --ef 48

The ANN family serves the arch's own deployment: the corpus width and N
come from its config and ``--shape`` (one of its ``ANN_SHAPES``, default
``search_300k``; ``--n`` cuts N), the data is generated from seed 0,
and the index is the config's tuned pipeline
(``IndexParams.from_config``). ``--spec`` swaps in any factory spec the
registry knows ("Flat", "IVF128", "IVFPQ64x16", "HNSW32", "NSG32,EP16",
with an optional "PCA<d>," prefix) with no code changes.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, list_archs
from repro.data import clustered_vectors, lm_batch, queries_like, recsys_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import recsys, transformer
from repro.serve.serve_step import (
    ann_search_step, lm_decode_step, lm_prefill_step, recsys_retrieval_step,
    recsys_score_step,
)

# build-time knobs the CLI can override on the config's pipeline or a spec
ANN_OVERRIDES = ("knn_backend", "finish_backend", "dist_backend", "rerank",
                 "hop_backend", "patience", "eps")


def ann_corpus(cfg, n: int, n_queries: int, seed: int = 0):
    """Seeded synthetic corpus at the config's width, and in-distribution
    queries: (data (n, cfg.dim), queries (n_queries, cfg.dim))."""
    key = jax.random.PRNGKey(seed)
    data = clustered_vectors(key, n, cfg.dim)
    queries = queries_like(jax.random.fold_in(key, 1), data, n_queries)
    return data, queries


def build_ann_index(cfg, data, key, spec=None, **overrides):
    """The config's tuned pipeline, or the factory ``spec`` when given.

    ``overrides`` (``ANN_OVERRIDES`` names; None = keep) replace fields of
    ``IndexParams.from_config(cfg)``, or pass through to ``build_index``.
    """
    from repro.core import IndexParams, TunedGraphIndex, build_index
    overrides = {k: v for k, v in overrides.items() if v is not None}
    if spec:
        return build_index(spec, data, key=key, **overrides)
    params = dataclasses.replace(IndexParams.from_config(cfg), **overrides)
    return TunedGraphIndex(params).fit(data, key)


def serve_ragged(step, queries, max_request: int, window_s: float = 0.0,
                 seed: int = 0):
    """Stream ``queries`` through a ``MicroBatchQueue`` as ragged requests
    of 1..max_request rows.

    Returns (queue, answers, seconds): ``answers`` holds each request's
    (start row, rows, answer), the answer being (dists, ids) or the
    queue's ``SearchFailure``.
    """
    from repro.serve.batching import MicroBatchQueue
    queue = MicroBatchQueue(step, window_s=window_s)
    rng = np.random.default_rng(seed)
    tickets, row = [], 0
    t0 = time.perf_counter()
    while row < queries.shape[0]:
        n = min(int(rng.integers(1, max_request + 1)),
                queries.shape[0] - row)
        tickets.append((queue.submit(queries[row:row + n]), row, n))
        row += n
        queue.maybe_flush()
    queue.flush()
    seconds = time.perf_counter() - t0
    return queue, [(row, n, queue.take(t)) for t, row, n in tickets], seconds


def serve_ann(args, arch):
    """Build (or restore) the ann arch's index and serve its corpus."""
    from repro.core import (
        FlatIndex, SearchParams, load_index, recall_at_k, save_index,
    )
    from repro.serve.batching import pow2_buckets
    cfg = arch.config
    shape = arch.shapes[args.shape]
    n = args.n or shape.n_candidates
    max_batch = args.batch or shape.batch
    data, queries = ann_corpus(cfg, n, 2 * max_batch)
    key = jax.random.PRNGKey(0)
    overrides = {name: getattr(args, name) for name in ANN_OVERRIDES}
    if args.restore:
        t_load = time.perf_counter()
        idx = load_index(args.restore)
        print(f"restored [{getattr(idx, 'spec', args.spec)}] from "
              f"{args.restore} in {time.perf_counter() - t_load:.2f}s "
              f"(checksums verified, invariants validated)")
        if hasattr(idx, "on_shard_error"):
            idx.on_shard_error = args.on_shard_error
    elif args.shards > 0:
        from repro.core.distributed import ShardedFactoryIndex
        if not args.spec:
            raise SystemExit("--shards shards a factory spec: pass --spec")
        idx = ShardedFactoryIndex(args.spec, n_shards=args.shards,
                                  on_shard_error=args.on_shard_error,
                                  **overrides)
        idx.fit(data, key=key)
    else:
        t_build = time.perf_counter()
        idx = build_ann_index(cfg, data, key, args.spec, **overrides)
        print(f"built {args.arch} N={n} dim={cfg.dim} in "
              f"{time.perf_counter() - t_build:.1f}s")
    if args.snapshot:
        save_index(idx, args.snapshot)
        print(f"snapshot saved to {args.snapshot} "
              f"(restore with --restore {args.snapshot})")
    injector = None
    if args.fault_rate > 0.0:
        # deterministic fault-injection demo: transient faults fire
        # UNDER the retry wrapper, so --retries absorbs them; armed
        # only after warmup so bucket compiles are fault-free
        from repro.serve.faults import FaultInjector
        injector = FaultInjector(seed=args.fault_seed)
        idx = injector.wrap_index(idx)
    spec_label = getattr(idx, "spec", None) or args.spec or "config pipeline"
    label = f"{args.arch} [{spec_label}]"
    if args.buckets == "off":
        buckets = None
    elif args.buckets == "auto":
        buckets = pow2_buckets(max_batch)
    else:
        buckets = tuple(int(b) for b in args.buckets.split(","))
    step = ann_search_step(idx, k=cfg.k,
                           params=SearchParams(
                               ef_search=args.ef or cfg.ef_search),
                           buckets=buckets,
                           retries=args.retries,
                           deadline_s=args.deadline)
    _, ti = FlatIndex(data).search(queries, cfg.k)
    if buckets is None:
        t0 = time.perf_counter()
        if injector is not None:
            injector.transient_rate = args.fault_rate
        _, ids = step(queries)
        jax.block_until_ready(ids)
        dt = time.perf_counter() - t0
        print(f"{label}: {queries.shape[0] / dt:.0f} "
              f"QPS, recall@{cfg.k}={recall_at_k(ids, ti):.4f}")
        return
    # bucketed serving: warm every bucket shape, then stream ragged
    # request batches through the micro-batching queue
    step.warmup(idx.dim)
    if injector is not None:
        injector.transient_rate = args.fault_rate   # arm AFTER warmup
    n_warm = len(step.dispatched)
    queue, answers, dt = serve_ragged(step, queries, max(1, max_batch // 8),
                                      args.batch_window)
    ids = np.full((queries.shape[0], cfg.k), -1, np.int64)
    failed_tickets = 0
    for start, rows, res in answers:
        if res:                         # SearchFailure is falsy
            ids[start:start + rows] = res[1]
        else:
            failed_tickets += 1
    shapes = sorted(set(step.dispatched[n_warm:]))
    print(f"{label} bucketed "
          f"(window={args.batch_window}s, buckets={list(step.buckets)}):"
          f" {queries.shape[0] / dt:.0f} QPS, "
          f"recall@{cfg.k}={recall_at_k(jnp.asarray(ids), ti):.4f}, "
          f"served shapes={shapes} (all pre-warmed)")
    lat = queue.latency_stats()
    print(f"  latency p50={lat['p50_ms']:.2f}ms "
          f"p99={lat['p99_ms']:.2f}ms mean={lat['mean_ms']:.2f}ms "
          f"over {lat['served']} queries / {lat['flushes']} flushes, "
          f"batch occupancy={lat['mean_occupancy']:.2f}")
    if injector is not None:
        print(f"  faults: {injector.faults_raised} injected "
              f"(rate={args.fault_rate}, seed={args.fault_seed}), "
              f"{getattr(step, 'retries_used', 0)} absorbed by retry")
    if lat["errors"] or lat["retries"] or lat["shed"] or failed_tickets:
        print(f"  resilience: {failed_tickets} failed tickets, "
              f"{lat['errors']} error answers, {lat['retries']} flush "
              f"retries, {lat['shed']} shed "
              f"(every ticket answered: result or typed failure)")
    degraded = getattr(idx, "degraded_shards", 0)
    if degraded:
        print(f"  degraded: {degraded} shard(s) masked on the last "
              f"search (on_shard_error=skip)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--batch", type=int, default=None,
                    help="batch size (default 8); for the ann family the "
                         "largest serving bucket (default: the shape's "
                         "batch), requests carrying up to 1/8 of it")
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--shape", default="search_300k",
                    help="ANN_SHAPES cell whose N and batch are served "
                         "(ann family only)")
    ap.add_argument("--n", type=int, default=None,
                    help="cut the corpus to N rows (ann family only; "
                         "default: the shape's N)")
    ap.add_argument("--spec", default=None,
                    help="ANN factory spec string (ann family only; "
                         "default: the config's tuned pipeline)")
    ap.add_argument("--ef", type=int, default=None,
                    help="SearchParams.ef_search override (ann family only; "
                         "default: the config's ef_search)")
    ap.add_argument("--batch-window", type=float, default=0.0,
                    help="micro-batching window in seconds; 0 serves each "
                         "request batch immediately (ann family only)")
    ap.add_argument("--buckets", default="auto",
                    help="comma-separated batch-shape buckets, or 'auto' "
                         "for powers of two up to --batch, or 'off' "
                         "(ann family only)")
    ap.add_argument("--knn-backend", default=None,
                    choices=["exact", "nndescent", "auto"],
                    help="override the build-time kNN-graph backend for "
                         "graph specs (ann family only); the spec's ,ND<K> "
                         "suffix is the in-grammar equivalent")
    ap.add_argument("--finish-backend", default=None,
                    choices=["host", "device", "auto"],
                    help="override the NSG finishing pass for graph specs "
                         "(ann family only): device jitted interconnect + "
                         "repair, or the host numpy parity path")
    ap.add_argument("--dist-backend", default=None,
                    choices=["f32", "pq", "int8"],
                    help="quantized-traversal serving for graph specs (ann "
                         "family only): traverse uint8 codes + exact-rerank "
                         "the beam tail; the spec's ,PQ<m>x8 / ,SQ8 suffix "
                         "is the in-grammar equivalent")
    ap.add_argument("--rerank", type=int, default=None,
                    help="exact-rerank depth of the quantized beam tail "
                         "(ann family only); ,Rerank<k> in-grammar")
    ap.add_argument("--hop-backend", default=None,
                    choices=["staged", "fused", "auto"],
                    help="beam-hop serving backend for graph specs (ann "
                         "family only): staged ops or the fused "
                         "kernels/beam_hop launch; ,HopFused / ,HopStaged "
                         "in-grammar")
    ap.add_argument("--patience", type=int, default=None,
                    help="adaptive early termination for graph specs (ann "
                         "family only): a lane stops after this many hops "
                         "without top-k improvement; ,Adapt<p> in-grammar")
    ap.add_argument("--eps", type=float, default=None,
                    help="minimum top-k distance improvement that counts as "
                         "progress for --patience (ann family only)")
    ap.add_argument("--snapshot", default=None, metavar="DIR",
                    help="save a checksummed index snapshot to DIR after "
                         "the build (core.persist.save_index; ann family "
                         "only)")
    ap.add_argument("--restore", default=None, metavar="DIR",
                    help="load the index from a snapshot DIR instead of "
                         "building (checksums verified + invariants "
                         "validated on load; ann family only)")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve through a ShardedFactoryIndex with this "
                         "many row shards (0 = unsharded; ann family only)")
    ap.add_argument("--on-shard-error", default="raise",
                    choices=["raise", "skip"],
                    help="sharded degraded-search policy: 'skip' masks a "
                         "failed shard's lanes to +inf and serves the "
                         "top-k from survivors (ann family only)")
    ap.add_argument("--retries", type=int, default=0,
                    help="bounded-retry attempts around each search flush "
                         "(serve.resilience.ResilientSearch; ann family "
                         "only)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-flush wall-clock deadline in seconds for "
                         "--retries (ann family only)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="inject seeded transient search faults at this "
                         "per-flush probability (resilience demo; ann "
                         "family only)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for --fault-rate's deterministic schedule")
    args = ap.parse_args()
    enable_compile_cache()
    spec = get_arch(args.arch)
    if spec.family == "ann":
        serve_ann(args, spec)
        return
    cfg = spec.smoke_config
    key = jax.random.PRNGKey(0)
    args.batch = args.batch or 8

    if spec.family == "lm":
        params = transformer.init_params(key, cfg)
        toks = lm_batch(key, args.batch, 32, cfg.vocab_size)["tokens"]
        prefill = jax.jit(lm_prefill_step(cfg))
        decode = jax.jit(lm_decode_step(cfg))
        t0 = time.perf_counter()
        last, cache = prefill(params, toks)
        out = [jnp.argmax(last, -1).astype(jnp.int32)]
        pos = jnp.full((args.batch,), toks.shape[1], jnp.int32)
        for _ in range(args.tokens - 1):
            logits, cache = decode(params, out[-1], cache, pos)
            out.append(jnp.argmax(logits, -1).astype(jnp.int32))
            pos = pos + 1
        jax.block_until_ready(out[-1])
        dt = time.perf_counter() - t0
        print(f"{args.arch}: prefill(32) + decode({args.tokens}) for "
              f"batch {args.batch} in {dt:.2f}s "
              f"({args.batch * args.tokens / dt:.1f} tok/s)")
    elif spec.family == "recsys":
        fam = recsys.family_of(cfg)
        params = recsys.INIT[fam](key, cfg)
        batch = recsys_batch(key, args.batch, cfg)
        score = jax.jit(recsys_score_step(cfg))
        s = score(params, batch)
        b1 = recsys_batch(key, 1, cfg)
        top, ids = jax.jit(recsys_retrieval_step(cfg, k=5))(
            params, b1, jnp.arange(512, dtype=jnp.int32))
        print(f"{args.arch}: scored batch {args.batch} "
              f"(mean {float(np.mean(np.asarray(s))):.4f}); retrieval "
              f"top5 ids {np.asarray(ids)}")
    else:
        raise SystemExit("gnn serving = scoring; use launch/train.py")


if __name__ == "__main__":
    main()
