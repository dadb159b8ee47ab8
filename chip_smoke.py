"""Prove the ann-laion build and serve path runs on a TPU.

    python3 chip_smoke.py              # one chip: build + serve ann-laion
    python3 chip_smoke.py --chips 4    # four chips: the sharded index only

One chip: check the main-path Pallas kernels (``gather_dist``,
``lut_dist``, ``beam_hop`` f32 and PQ) against their jnp refs bit for bit
at the serve widths; build the paper's tuned pipeline
(``configs/ann_laion.py``: 768-d, PCA600, AntiHub 0.9, EP64, NSG32) over a
seeded synthetic corpus, warm the serve step's batch buckets, stream
ragged requests through the micro-batching queue with f32 and PQ
traversal, and check recall@10 against exact brute force (``FlatIndex``)
over the same rows. Everything runs through the serve launcher's own
functions (``repro.launch.serve``).

N defaults to 150,000, the deployment's 300,000 halved once: a 300k run
took 723 s of the 1,200 s budget with a warm compile cache, and a cold
one compiles for minutes more.

``--chips 4``: build ``ShardedIndex`` on a (1, 4) mesh, one shard per
chip, compare it with brute force over the same rows, and print where
each shard lives, each device's bytes in use and the arrays that hold
them.

The script exits non-zero, with no result line, when JAX's device is not
a TPU or any check fails; it catches nothing. The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import logging
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
RECALL_FLOOR = 0.9        # the tuner's floor (core/tuning/objective.py)
SEED = 0
DEFAULT_N = 150_000       # search_300k's N halved once to fit a cold run


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    """A failed check ends the run (unlike ``assert``, kept under ``-O``)."""
    if not ok:
        raise SystemExit(f"chip_smoke check failed: {msg}")


def tpu_devices(chips: int):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke needs a TPU; JAX's platform is {platform!r}")
    if len(devices) < chips:
        sys.exit(f"--chips {chips} needs {chips} TPU devices, JAX sees "
                 f"{len(devices)}")
    return devices


def kernel_parity(n_rows: int) -> None:
    """Each main-path kernel against its jnp ref on the chip, bit for bit.

    Random tables at the ann-laion serve widths (``n_rows`` rows of d'
    f32 and of PQ codes, a full batch of R candidate ids per query with
    some -1, ef-wide pools that already hold some candidates). A row
    read from the wrong tile, or before its DMA landed, changes a value.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch
    from repro.core.quant.codec import default_pq_m
    from repro.kernels.beam_hop import beam_hop
    from repro.kernels.gather_dist import gather_dist
    from repro.kernels.lut_dist import lut_dist
    from repro.kernels.row_gather import centroid_major

    arch = get_arch("ann-laion")
    cfg, q = arch.config, arch.shapes["search_300k"].batch
    d, r, ef, m = (cfg.pca_dim, cfg.graph_degree, cfg.ef_search,
                   default_pq_m(cfg.pca_dim))
    t0 = time.perf_counter()
    k = jax.random.split(jax.random.PRNGKey(SEED), 10)

    def ids_with_holes(key, shape):
        ids = jax.random.randint(key, shape, 0, n_rows)
        return jnp.where(jax.random.uniform(jax.random.fold_in(key, 1),
                                            shape) < 0.1, -1, ids)

    db = jax.random.normal(k[0], (n_rows, d), jnp.float32)
    codes = jax.random.randint(k[1], (n_rows, m), 0, 256).astype(jnp.uint8)
    queries = jax.random.normal(k[2], (q, d), jnp.float32)
    lut = jax.random.uniform(k[3], (q, m, 256), jnp.float32)
    ids = ids_with_holes(k[4], (q, r))
    neighbors = ids_with_holes(k[5], (n_rows, r))
    sel = ids_with_holes(k[6], (q,))
    pool_i = ids_with_holes(k[7], (q, ef))
    pool_i = pool_i.at[:, :r // 4].set(
        neighbors[jnp.maximum(sel, 0), :r // 4])      # dedup must fire
    pool_d = jnp.sort(jax.random.uniform(k[8], (q, ef), jnp.float32,
                                         0, 2 * d), axis=1)
    pool_d = jnp.where(pool_i >= 0, pool_d, jnp.inf)
    pool_v = jax.random.bernoulli(k[9], 0.5, (q, ef))
    jax.block_until_ready((db, codes, lut, pool_i, pool_v))
    log(f"parity: {n_rows} rows, d'={d}, PQ{m}, R={r}, ef={ef}, Q={q} "
        f"({time.perf_counter() - t0:.1f}s to make)")

    # the kernels take the ADC table centroid-major, the refs (Q, M, C)
    lut_for = {"jnp": lut,
               "pallas": jax.block_until_ready(centroid_major(lut))}
    cases = {
        "gather_dist": lambda b: [gather_dist(queries, db, ids, backend=b)],
        "lut_dist": lambda b: [lut_dist(lut_for[b], codes, ids, backend=b)],
        "beam_hop_f32": lambda b: beam_hop(
            sel, neighbors, pool_i, pool_d, pool_v, queries, db,
            dist_backend="f32", backend=b),
        "beam_hop_pq": lambda b: beam_hop(
            sel, neighbors, pool_i, pool_d, pool_v, lut_for[b], codes,
            dist_backend="pq", backend=b),
    }
    bad = []
    for name, run in cases.items():
        t = time.perf_counter()
        got = jax.block_until_ready(run("pallas"))
        t_kernel = time.perf_counter() - t
        want = jax.block_until_ready(run("jnp"))
        log(f"parity {name}: kernel {t_kernel:.1f}s, ref "
            f"{time.perf_counter() - t - t_kernel:.1f}s (compile included)")
        for j, (g, w) in enumerate(zip(got, want)):
            g, w = np.asarray(g), np.asarray(w)
            diff = g != w
            msg = (f"parity {name}[{j}]: {int(diff.sum())} of {diff.size} "
                   f"differ")
            if diff.any() and g.dtype == np.float32:
                fin = np.isfinite(g) & np.isfinite(w)
                ulps = np.abs(g.view(np.int32).astype(np.int64)
                              - w.view(np.int32).astype(np.int64))[fin]
                msg += (f" ({int((diff & ~fin).sum())} non-finite, max "
                        f"{int(ulps.max(initial=0))} ulp)")
            log(msg)
            if diff.any():
                bad.append(f"{name}[{j}]")
    check(not bad, f"kernels differ from their refs: {bad}")


def one_chip(n: int) -> None:
    import jax
    import numpy as np
    from repro.configs import get_arch
    from repro.core import FlatIndex, SearchParams, recall_at_k
    from repro.core.beam_search import (
        resolve_gather_backend, resolve_hop_backend,
    )
    from repro.kernels.topk_merge import resolve_merge_backend
    from repro.launch.serve import ann_corpus, build_ann_index, serve_ragged
    from repro.serve.batching import pow2_buckets
    from repro.serve.serve_step import ann_search_step

    arch = get_arch("ann-laion")
    cfg, shape = arch.config, arch.shapes["search_300k"]
    backends = (resolve_hop_backend(None), resolve_gather_backend(None),
                resolve_merge_backend(None))
    log(f"backends: hop={backends[0]} gather={backends[1]} "
        f"merge={backends[2]}")
    check(backends == ("fused", "pallas", "pallas"), f"backends {backends}")

    if n != shape.n_candidates:
        log(f"N cut from {shape.n_candidates} to {n}")
    kernel_parity(round(n * cfg.antihub_keep))
    t = time.perf_counter()
    data, queries = ann_corpus(cfg, n, 2 * shape.batch, SEED)
    jax.block_until_ready((data, queries))
    log(f"corpus: N={n} dim={cfg.dim} queries={queries.shape[0]} "
        f"({time.perf_counter() - t:.1f}s)")

    t = time.perf_counter()
    idx = build_ann_index(cfg, data, jax.random.PRNGKey(SEED))
    log(f"build: {time.perf_counter() - t:.1f}s, {idx.ntotal} rows kept, "
        f"d'={idx.base.shape[1]}, R={idx.graph.neighbors.shape[1]}")
    st = idx.build_stats
    nsg = {"pools": st.pools_seconds, "prune": st.prune_seconds,
           "interconnect": st.interconnect_seconds,
           "repair": st.repair_seconds}
    log("build stages (s): " + ", ".join(
        f"{k}={v:.1f}" for k, v in idx.stage_seconds.items())
        + "; nsg: " + ", ".join(f"{k}={v:.1f}" for k, v in nsg.items()))

    t = time.perf_counter()
    idx.quantize("pq")
    jax.block_until_ready(idx.codes)
    log(f"quantize: PQ{idx.codes.shape[1]}x8 in "
        f"{time.perf_counter() - t:.1f}s")

    _, truth = FlatIndex(data).search(queries, cfg.k)
    buckets = pow2_buckets(shape.batch)
    for backend in ("f32", "pq"):
        params = SearchParams(ef_search=cfg.ef_search, dist_backend=backend)
        step = ann_search_step(idx, cfg.k, params, buckets=buckets)
        t = time.perf_counter()
        step.warmup(idx.dim)
        log(f"{backend}: warmed buckets {list(buckets)} in "
            f"{time.perf_counter() - t:.1f}s")

        lowered = jax.jit(functools.partial(
            idx.search, k=cfg.k, params=params)).lower(queries[:8])
        kernels = lowered.as_text().count("tpu_custom_call")
        log(f"{backend}: serve step lowers {kernels} tpu_custom_call ops")
        check(kernels > 0, "the serve step runs no compiled kernel")

        queue, answers, seconds = serve_ragged(
            step, queries, shape.batch // 8, seed=SEED)
        failed = [a for a in answers if not a[2]]
        check(not failed, f"{len(failed)} tickets failed: {failed[:1]}")
        ids = np.concatenate([np.asarray(a[2][1]) for a in answers])
        recall = float(recall_at_k(ids, truth))
        lat = queue.latency_stats()
        log(f"{backend}: {len(answers)} tickets answered over "
            f"{lat['flushes']} flushes, recall@{cfg.k}={recall:.4f} at "
            f"ef={cfg.ef_search}; single smoke reading, not a benchmark: "
            f"{queries.shape[0] / seconds:.0f} QPS, "
            f"p50={lat['p50_ms']:.1f}ms p99={lat['p99_ms']:.1f}ms")
        if backend == "f32":
            check(recall >= RECALL_FLOOR,
                  f"f32 recall@{cfg.k} {recall:.4f} < {RECALL_FLOOR}")


def live_arrays_by_device() -> None:
    """Log each device's live JAX arrays, the 8 largest (shape, dtype)."""
    import collections
    import jax
    held = collections.defaultdict(collections.Counter)
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            held[shard.device][(shard.data.shape, str(a.dtype))] += \
                shard.data.nbytes
    for dev in sorted(held, key=lambda d: d.id):
        log(f"  {dev}: {sum(held[dev].values())} bytes in live arrays; "
            "largest: " + ", ".join(
                f"{dt}{list(shape)}={b}"
                for (shape, dt), b in held[dev].most_common(8)))


def four_chips(n_per_shard: int) -> None:
    import jax
    from repro.configs import get_arch
    from repro.core import FlatIndex, IndexParams, recall_at_k
    from repro.core.distributed import ShardedIndex
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import ann_corpus

    arch = get_arch("ann-laion")
    cfg, shape = arch.config, arch.shapes["search_300k"]
    mesh = make_host_mesh(data=1, model=4)
    n = 4 * n_per_shard
    data, queries = ann_corpus(cfg, n, shape.batch, SEED)
    jax.block_until_ready((data, queries))
    before = {d.id: d.memory_stats()["bytes_in_use"] for d in jax.devices()}
    log(f"corpus: N={n} ({n_per_shard} per shard) dim={cfg.dim} on "
        f"mesh {dict(mesh.shape)}")

    t = time.perf_counter()
    idx = ShardedIndex(IndexParams.from_config(cfg), mesh).fit(
        data, jax.random.PRNGKey(SEED))
    log(f"sharded build: {time.perf_counter() - t:.1f}s")
    for i, st in enumerate(idx.shard_stats):
        log(f"  shard {i}: " + ", ".join(
            f"{k}={v:.1f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in st.items()))

    _, d_ids = idx.search(queries, cfg.k)
    _, truth = FlatIndex(data).search(queries, cfg.k)
    recall = float(recall_at_k(d_ids, truth))
    log(f"sharded recall@{cfg.k}={recall:.4f} at ef={cfg.ef_search} "
        f"against brute force over the same {n} rows")

    for shard in idx.arrays.base.addressable_shards:
        log(f"  base rows {shard.index[0]} on {shard.device}")
    for d in jax.devices():
        used = d.memory_stats()["bytes_in_use"]
        log(f"  {d}: bytes_in_use={used} "
            f"(+{used - before[d.id]} since the corpus was made)")
    live_arrays_by_device()
    homes = {s.device for s in idx.arrays.base.addressable_shards}
    check(len(homes) == 4, f"shards sit on {len(homes)} devices")
    check(recall >= RECALL_FLOOR,
          f"sharded recall@{cfg.k} {recall:.4f} < {RECALL_FLOOR}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=DEFAULT_N,
                    help="corpus rows (per shard with --chips 4)")
    args = ap.parse_args()
    devices = tpu_devices(args.chips)
    sys.path.insert(0, str(SRC))
    logging.basicConfig(format="%(message)s", stream=sys.stdout)
    logging.getLogger("repro").setLevel(logging.INFO)   # build progress
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(args.n)
    else:
        one_chip(args.n)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
