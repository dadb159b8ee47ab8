"""Recall-QPS trade-off curves (the x-axes of the paper's Fig. 1/3): sweep
each index family's runtime knob and emit (recall, QPS) points. With the
unified Index API a sweep is just (factory spec, SearchParams field, values)
— the loop below works for any registered family.

Output lands twice: a plot-ready table under benchmarks/results/, and
``BENCH_qps.json`` at the repo root — the accumulating perf trajectory that
CI uploads per commit, so QPS tuning claims are checked against history
instead of vibes. Scale via BENCH_N / BENCH_DIM / BENCH_Q env vars (the CI
bench-smoke runs a tiny instance of exactly this file).
"""
from __future__ import annotations

import os

from benchmarks.common import (
    DIM, K, dataset, measure_qps, print_table, save, save_bench_json,
)
from repro.core import SearchParams, build_index, default_pq_m, recall_at_k

# (spec, tunable SearchParams field, sweep values). HNSW's sequential host
# build dominates at large BENCH_N; skip it above the cutoff so full-scale
# NSG/IVF sweeps don't wait minutes on an insert loop.
SWEEPS = [
    ("NSG24,EP32", "ef_search", (16, 32, 64, 128)),
    ("IVF128,Flat", "nprobe", (1, 4, 16, 64)),
    # PQ subquantizer count must divide BENCH_DIM (96 and the smoke 32 are
    # both divisible by 8) — the factory now rejects mismatches at parse
    # time instead of quietly pinning recall.
    ("IVFPQ48x8", "nprobe", (4, 16)),
    ("HNSW16,EP16", "ef_search", (16, 64)),
]
HNSW_BUILD_CUTOFF = int(os.environ.get("BENCH_HNSW_MAX_N", 5000))

# Quantized traversal vs its f32 twin at MATCHED ef_search values: the two
# sweeps share graph structure and beam width, so at each ef the recall is
# near-identical and qps_pq / qps_f32 reads off the iso-recall speedup
# directly (the first sweep above provides the f32 curve; PQ code size
# auto-tracks BENCH_DIM so the spec stays valid at smoke scale).
QUANT_EF_VALUES = (16, 32, 64, 128)
QUANT_SWEEPS = [
    (f"NSG24,EP32,PQ{default_pq_m(DIM)}x8,Rerank64", "pq"),
    ("NSG24,EP32,SQ8,Rerank64", "int8"),
]

# Adaptive-termination sweep (``stage="adaptive_term"`` in BENCH_qps.json):
# the pinned NSG24,EP32 ef-sweep rerun with patience against the
# patience=None baseline at each ef. CI gates on >= 1.3x fewer total hops
# at a recall delta >= -0.005 for at least one point.
ADAPTIVE_SPEC = "NSG24,EP32"
ADAPTIVE_EF_VALUES = (16, 32, 64, 128)
# patience=8 shows the aggressive end of the trade; patience=24 is the
# conservative point that clears the CI gate (>= 1.3x fewer hops within
# 0.5pt recall) at both the committed 20k scale and the 1500-point smoke.
ADAPTIVE_PATIENCE = (8, 24)


def adaptive_term_points(data, queries, true_i):
    """Straggler-control sweep at the pinned spec: one baseline point plus
    one adaptive point per patience value, per ef.

    ``total_hops`` counts hop-loop iterations the batch actually executed —
    useful hops plus the lock-stepped no-op hops converged lanes rode
    (``wasted_hops``). Adaptive points carry ``hop_reduction_vs_baseline``
    (baseline total / adaptive total) and ``recall_delta`` against the
    patience=None run at the same ef: the two numbers the CI gate reads.
    """
    idx = build_index(ADAPTIVE_SPEC, data)
    k = true_i.shape[1]
    points = []
    for ef in ADAPTIVE_EF_VALUES:
        base = SearchParams(ef_search=ef)
        _, i = idx.search(queries, k, base)
        base_rec = float(recall_at_k(i, true_i))
        bs = idx.search_stats()
        base_total = bs["hops"] + bs["wasted_hops"]
        base_qps = measure_qps(lambda q: idx.search(q, K, base)[0],
                               queries, repeats=3)
        points.append({
            "stage": "adaptive_term", "spec": ADAPTIVE_SPEC, "ef": ef,
            "patience": 0, "eps": 0.0,
            "recall": round(base_rec, 4), "qps": round(base_qps, 1),
            "total_hops": base_total, "useful_hops": bs["hops"],
            "wasted_hops": bs["wasted_hops"],
            "mean_hops": round(bs["mean_hops"], 2),
            "p99_hops": round(bs["p99_hops"], 2),
        })
        for patience in ADAPTIVE_PATIENCE:
            params = SearchParams(ef_search=ef, patience=patience)
            _, i = idx.search(queries, k, params)
            rec = float(recall_at_k(i, true_i))
            s = idx.search_stats()
            total = s["hops"] + s["wasted_hops"]
            qps = measure_qps(lambda q: idx.search(q, K, params)[0],
                              queries, repeats=3)
            points.append({
                "stage": "adaptive_term", "spec": ADAPTIVE_SPEC, "ef": ef,
                "patience": patience, "eps": 0.0,
                "recall": round(rec, 4), "qps": round(qps, 1),
                "total_hops": total, "useful_hops": s["hops"],
                "wasted_hops": s["wasted_hops"],
                "mean_hops": round(s["mean_hops"], 2),
                "p99_hops": round(s["p99_hops"], 2),
                "active_fraction": round(s["active_fraction"], 4),
                "hop_reduction_vs_baseline":
                    round(base_total / max(total, 1), 3),
                "recall_delta": round(rec - base_rec, 4),
            })
    return points


# Fault-tolerance stage (``stage="serve_resilience"`` in BENCH_qps.json):
# snapshot-load vs rebuild wall-clock at the pinned spec, a fault-injected
# serve run's ticket accounting (the zero-lost-tickets contract), and
# degraded sharded recall with one shard dead. CI gates on the stage being
# present, load_speedup >= 10x, and tickets_lost == 0.
RESILIENCE_SPEC = "NSG24,EP32"
RESILIENCE_EF = 32
RESILIENCE_SHARDS = 8
RESILIENCE_FAULT_RATE = 0.25


def serve_resilience_points(data, queries, true_i):
    """One combined point: persistence speedup + resilient-serve ticket
    accounting + degraded-shard recall.

    * ``load_speedup`` — rebuild seconds / snapshot-load seconds at this N
      (the operational claim: restarting a server from a snapshot must be
      >= 10x cheaper than rebuilding, even at smoke scale);
    * ``load_identical`` — the restored index's ids match the builder's
      bit-for-bit (the persistence contract, measured not assumed);
    * ``tickets_*`` — a micro-batched serve run under seeded transient
      faults (rate ``RESILIENCE_FAULT_RATE``, absorbed by flush retry +
      ``ResilientSearch``): every submitted ticket must come back as a
      result or a typed failure, so ``tickets_lost`` is 0 by contract;
    * ``recall_degraded`` / ``recall_retained`` — the same sharded index
      with 1 of ``RESILIENCE_SHARDS`` sub-indexes permanently dead,
      searched with ``on_shard_error="skip"``: survivors' merged top-k
      recall, and its ratio to the healthy sharded recall.
    """
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from repro.core import load_index, save_index
    from repro.core.distributed import ShardedFactoryIndex
    from repro.serve.batching import MicroBatchQueue, pow2_buckets
    from repro.serve.faults import FaultInjector
    from repro.serve.serve_step import ann_search_step

    k = true_i.shape[1]
    params = SearchParams(ef_search=RESILIENCE_EF)

    # --- snapshot-load vs rebuild -------------------------------------
    t0 = _time.perf_counter()
    idx = build_index(RESILIENCE_SPEC, data)
    build_s = _time.perf_counter() - t0
    snap = tempfile.mkdtemp(prefix="bench_resilience_snap_")
    try:
        save_index(idx, snap)
        t0 = _time.perf_counter()
        restored = load_index(snap)
        load_s = _time.perf_counter() - t0
        _, i_build = idx.search(queries, k, params)
        d_rest, i_rest = restored.search(queries, k, params)
        identical = bool(np.array_equal(np.asarray(i_build),
                                        np.asarray(i_rest)))
    finally:
        shutil.rmtree(snap, ignore_errors=True)

    # --- fault-injected resilient serve -------------------------------
    inj = FaultInjector(seed=0, transient_rate=0.0)
    faulty = inj.wrap_index(restored)
    step = ann_search_step(faulty, k=k, params=params,
                           buckets=pow2_buckets(32), retries=4)
    step.warmup(restored.dim)
    inj.transient_rate = RESILIENCE_FAULT_RATE    # arm AFTER warmup
    queue = MicroBatchQueue(step, window_s=0.0, flush_retries=1)
    rng = np.random.default_rng(0)
    tickets, row = [], 0
    while row < queries.shape[0]:
        n = min(int(rng.integers(1, 9)), queries.shape[0] - row)
        tickets.append(queue.submit(queries[row:row + n]))
        row += n
        queue.maybe_flush()
    queue.flush()
    ok = failed = 0
    for t in tickets:
        res = queue.take(t)
        if res:
            ok += 1
        else:
            failed += 1
    lost = len(tickets) - ok - failed
    lat = queue.latency_stats()

    # --- degraded sharded search (1 of RESILIENCE_SHARDS dead) ---------
    sharded = ShardedFactoryIndex(RESILIENCE_SPEC,
                                  n_shards=RESILIENCE_SHARDS,
                                  on_shard_error="skip")
    sharded.fit(data)
    _, i_full = sharded.search(queries, k, params)
    rec_full = float(recall_at_k(i_full, true_i))
    dead = FaultInjector(seed=0, permanent_rate=1.0)
    sharded.subs[0] = dead.wrap_index(sharded.subs[0])
    _, i_deg = sharded.search(queries, k, params)
    rec_deg = float(recall_at_k(i_deg, true_i))

    return [{
        "stage": "serve_resilience", "spec": RESILIENCE_SPEC,
        "ef": RESILIENCE_EF, "n": int(data.shape[0]),
        "build_seconds": round(build_s, 4),
        "load_seconds": round(load_s, 4),
        "load_speedup": round(build_s / max(load_s, 1e-9), 1),
        "load_identical": identical,
        "fault_rate": RESILIENCE_FAULT_RATE,
        "tickets_submitted": len(tickets),
        "tickets_ok": ok, "tickets_failed": failed, "tickets_lost": lost,
        "flush_retries": lat["retries"], "flush_errors": lat["errors"],
        "faults_injected": int(inj.faults_raised),
        "resilient_retries": int(getattr(step, "retries_used", 0)),
        "n_shards": RESILIENCE_SHARDS,
        "degraded_shards": int(sharded.degraded_shards),
        "recall_full": round(rec_full, 4),
        "recall_degraded": round(rec_deg, 4),
        "recall_retained": round(rec_deg / max(rec_full, 1e-9), 4),
    }]


def _merge_stage_points(stage, points, path=None):
    """Replace one ``stage=...`` section of BENCH_qps.json in place (same
    read-modify-write contract as kernel_bench.merge_beam_hop_points: a
    standalone regen must not clobber the other sweeps)."""
    import json

    from benchmarks.common import N_DB, N_QUERIES, REPO_ROOT
    import jax

    path = path or os.path.join(REPO_ROOT, "BENCH_qps.json")
    doc = {"backend": jax.default_backend(),
           "dataset": {"n": N_DB, "dim": DIM, "n_queries": N_QUERIES,
                       "k": K},
           "points": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            pass
    doc["points"] = [p for p in doc.get("points", [])
                     if p.get("stage") != stage] + points
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    return path


def merge_serve_resilience_points(points, path=None):
    """Replace the stage='serve_resilience' section of BENCH_qps.json."""
    return _merge_stage_points("serve_resilience", points, path)


def merge_adaptive_term_points(points, path=None):
    """Replace the stage='adaptive_term' section of BENCH_qps.json."""
    return _merge_stage_points("adaptive_term", points, path)


def run():
    data, queries, ti = dataset()
    points, rows = [], []

    def sweep(spec, knob, values, dist_backend="f32"):
        idx = build_index(spec, data)
        assert knob in idx.search_params_space().names(), (spec, knob)
        for v in values:
            params = SearchParams(**{knob: v})
            d, i = idx.search(queries, K, params)
            r = float(recall_at_k(i, ti))
            qps = measure_qps(lambda q: idx.search(q, K, params)[0],
                              queries, repeats=3)
            point = {
                "spec": spec, "knob": knob, "value": v,
                "recall": round(r, 4), "qps": round(qps, 1),
                "mem_mb": round(idx.memory_bytes() / 1e6, 2),
                "dist_backend": dist_backend,
            }
            stats = getattr(idx, "search_stats", lambda: None)()
            if stats:                    # graph indexes: hop distribution
                point["mean_hops"] = round(stats["mean_hops"], 2)
                point["p99_hops"] = round(stats["p99_hops"], 2)
            points.append(point)
            rows.append([f"{spec} {knob}={v}", round(r, 4), f"{qps:.1f}",
                         f"mem {idx.memory_bytes()/1e6:.1f}MB"])

    for spec, knob, values in SWEEPS:
        if spec.startswith("HNSW") and data.shape[0] > HNSW_BUILD_CUTOFF:
            print(f"skip {spec}: N={data.shape[0]} > "
                  f"BENCH_HNSW_MAX_N={HNSW_BUILD_CUTOFF}")
            continue
        sweep(spec, knob, values)
    for spec, backend in QUANT_SWEEPS:
        sweep(spec, "ef_search", QUANT_EF_VALUES, dist_backend=backend)

    # matched-ef f32 vs quantized QPS ratios, directly readable in the log
    f32 = {p["value"]: p["qps"] for p in points
           if p["spec"] == "NSG24,EP32" and p["dist_backend"] == "f32"}
    for p in points:
        if p["dist_backend"] != "f32" and p["value"] in f32:
            p["qps_vs_f32"] = round(p["qps"] / f32[p["value"]], 2)
            rows.append([f"{p['spec']} ef={p['value']} vs f32",
                         p["recall"], f"{p['qps']:.1f}",
                         f"{p['qps_vs_f32']}x f32"])

    # fused vs staged beam hop at the pinned config (kernel_bench owns the
    # measurement + the per-hop traffic model; points carry the >= 2x
    # spilled-traffic gate CI asserts on)
    from benchmarks.kernel_bench import beam_hop_points
    bh = beam_hop_points(data, queries, ti)
    points.extend(bh)
    for p in bh:
        rows.append([f"{p['spec']} hop={p['hop_backend']} "
                     f"({p['dist_backend']})", p["recall"],
                     f"{p['qps']:.1f}",
                     f"spill {p['spilled_bytes_per_hop']}B/hop"])

    # adaptive termination vs the patience=None baseline at
    # the pinned sweep (carries the >= 1.3x total-hop gate CI asserts on)
    at = adaptive_term_points(data, queries, ti)
    points.extend(at)
    for p in at:
        tag = f"Adapt{p['patience']}" if p["patience"] else "baseline"
        extra = (f"{p['hop_reduction_vs_baseline']}x fewer hops, "
                 f"recall {p['recall_delta']:+.4f}"
                 if p["patience"] else f"{p['total_hops']} total hops")
        rows.append([f"{p['spec']} ef={p['ef']} {tag}", p["recall"],
                     f"{p['qps']:.1f}", extra])

    # persistence + fault-tolerance point (snapshot-load speedup, the
    # zero-lost-tickets serve contract, degraded sharded recall — carries
    # the load_speedup >= 10x gate CI asserts on)
    sr = serve_resilience_points(data, queries, ti)
    points.extend(sr)
    for p in sr:
        rows.append([f"{p['spec']} snapshot restore", p["recall_full"],
                     "-", f"{p['load_speedup']}x faster than rebuild, "
                          f"identical={p['load_identical']}"])
        rows.append([f"{p['spec']} faulty serve "
                     f"(rate={p['fault_rate']})", "-", "-",
                     f"{p['tickets_ok']} ok + {p['tickets_failed']} failed "
                     f"/ {p['tickets_submitted']} tickets, "
                     f"{p['tickets_lost']} lost"])
        rows.append([f"{p['spec']} x{p['n_shards']} shards, 1 dead",
                     p["recall_degraded"], "-",
                     f"{p['recall_retained']:.2%} of healthy recall"])

    headers = ["config", "recall@10", "QPS", ""]
    print_table("QPS-recall frontiers", headers, rows)
    save("qps_recall_curves", rows, headers)
    path = save_bench_json("qps", {"points": points})
    print(f"wrote {path}")
    return points


if __name__ == "__main__":
    import sys
    if "--resilience-only" in sys.argv:
        # regen just the stage="serve_resilience" section (read-modify-
        # write; the other sweeps in BENCH_qps.json are left untouched)
        _data, _queries, _ti = dataset()
        _pts = serve_resilience_points(_data, _queries, _ti)
        _path = merge_serve_resilience_points(_pts)
        print(f"merged {len(_pts)} serve_resilience points into {_path}")
    elif "--adaptive-only" in sys.argv:
        # regen just the stage="adaptive_term" section (read-modify-write;
        # the other sweeps in BENCH_qps.json are left untouched)
        _data, _queries, _ti = dataset()
        _pts = adaptive_term_points(_data, _queries, _ti)
        _path = merge_adaptive_term_points(_pts)
        print(f"merged {len(_pts)} adaptive_term points into {_path}")
    else:
        run()
