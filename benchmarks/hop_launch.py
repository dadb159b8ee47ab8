"""Device time of one fused beam-hop launch at the ann-laion serve widths.

    python3 benchmarks/hop_launch.py [--dist f32|pq] [--q 128 512 1024]
                                     [--launches 20] [--baseline FILE]

Runs ``beam_hop_pallas`` alone on a TPU over a 270,000-row table
(pre-padded, as the search hoists it), R=32 neighbour ids per node, ef=64
pools. ``--dist f32`` (the default) scores 600-wide f32 rows against the
queries; ``--dist pq`` scores PQ300x8 uint8 code rows through a (Q, 300,
256) f32 ADC table, the quantized cell's widths, handed to the kernel
centroid-major ((Q, 256, 300), made once, as the search hoists it). For
each batch size Q, two liveness patterns:

  * ``all``: every lane live, every neighbour slot valid;
  * ``cell``: the graph cell's liveness, 43% of lanes dead (``sel < 0``)
    and each node's row -1-padded past an out-degree drawn from 2..32
    (mean 17).

Each case is checked against ``beam_hop_ref`` bit for bit, warmed, then run
``--launches`` times under the profiler. One line per case gives the
kernel op's device time per launch, per grid step (Q / 8 queries) and per
candidate slot (Q x R), the device time of all the launch's ops, and the
share of slots that are live. The last
line is a JSON object with every case. ``--baseline FILE`` reads another
run's output (its last line; say, the same command on an older tree) and
adds to each case ``step_ratio``, this step time over that run's for the
same case. Exits non-zero off a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

N, D, R, EF, TB = 270_000, 600, 32, 64, 8
PQ_M, PQ_C = 300, 256
SEED = 20231003


def hop_inputs(nq: int, pattern: str, table_rows: int, dist: str = "f32"):
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(SEED + nq), 8)
    nbr = jax.random.randint(k[0], (table_rows, R), 0, table_rows)
    sel = jax.random.randint(k[1], (nq,), 0, table_rows)
    if pattern == "cell":
        deg = jax.random.randint(k[2], (table_rows, 1), 2, R + 1)
        nbr = jnp.where(jnp.arange(R)[None, :] < deg, nbr, -1)
        sel = jnp.where(jax.random.uniform(k[3], (nq,)) < 0.43, -1, sel)
    pool_i = jax.random.randint(k[4], (nq, EF), 0, table_rows)
    pool_d = jnp.sort(jax.random.uniform(k[5], (nq, EF), jnp.float32,
                                         0, 2 * D), axis=1)
    pool_v = jax.random.bernoulli(k[6], 0.5, (nq, EF))
    if dist == "f32":
        q = jax.random.normal(k[7], (nq, D), jnp.float32)
    else:                       # an ADC table: squared sub-distances
        q = jax.random.uniform(k[7], (nq, PQ_M, PQ_C), jnp.float32, 0, 4)
    return sel, nbr, pool_i, pool_d, pool_v, q


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dist", choices=("f32", "pq"), default="f32")
    ap.add_argument("--q", type=int, nargs="+", default=[128, 512, 1024])
    ap.add_argument("--launches", type=int, default=20)
    ap.add_argument("--baseline", type=Path,
                    help="another run's output, for each case's step_ratio")
    args = ap.parse_args()
    base = {}
    if args.baseline:
        last = args.baseline.read_text().strip().splitlines()[-1]
        base = {(c["q"], c["pattern"]): c["step_us"]
                for c in json.loads(last)["cases"]}

    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import trace
    from repro.kernels.beam_hop import beam_hop_pallas, beam_hop_ref
    from repro.kernels.row_gather import centroid_major, pad_table

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"hop_launch: needs a TPU, JAX's platform is "
                 f"{dev.platform!r}")
    if args.dist == "f32":
        db = jax.random.normal(jax.random.PRNGKey(SEED), (N, D))
    else:
        db = jax.random.randint(jax.random.PRNGKey(SEED), (N, PQ_M), 0,
                                PQ_C).astype(jnp.uint8)
    table = jax.block_until_ready(pad_table(db))
    kw = {"dist_backend": args.dist}
    results = []
    for nq in args.q:
        for pattern in ("all", "cell"):
            sel, nbr, pi, pd, pv, q = hop_inputs(nq, pattern, N, args.dist)
            q_kernel = q if args.dist == "f32" else jax.block_until_ready(
                centroid_major(q))
            hop_args = (sel, nbr, pi, pd, pv, q_kernel, table)
            got = jax.block_until_ready(
                beam_hop_pallas(*hop_args, interpret=False, **kw))
            want = beam_hop_ref(sel, nbr, pi, pd, pv, q, db, **kw)
            exact = all(bool(np.array_equal(np.asarray(a), np.asarray(b)))
                        for a, b in zip(want, got))
            live = float(np.mean((np.asarray(nbr)[np.maximum(
                np.asarray(sel), 0)] >= 0) & (np.asarray(sel) >= 0)[:, None]))
            with tempfile.TemporaryDirectory() as tmp:
                t = time.perf_counter()
                with jax.profiler.trace(tmp):
                    for _ in range(args.launches):
                        out = beam_hop_pallas(*hop_args, interpret=False,
                                              **kw)
                    jax.block_until_ready(out)
                host_s = time.perf_counter() - t
                ops, _, _ = trace.load(Path(tmp))
            by_op: dict = {}
            for e in ops:
                if e.program.startswith("jit_beam_hop_pallas"):
                    by_op.setdefault(e.name, []).append(e.dur_ns)
            # the kernel's op is %beam_hop_pallas.<n>
            op, hop = next((k, v) for k, v in by_op.items()
                           if k.startswith("%beam_hop_pallas"))
            per_launch_us = sum(hop) / len(hop) / 1e3
            row = {"dist": args.dist, "q": nq, "pattern": pattern,
                   "bit_exact": exact,
                   "live_share": round(live, 4), "launches": len(hop),
                   "launch_us": round(per_launch_us, 2),
                   "program_us": round(sum(map(sum, by_op.values()))
                                       / len(hop) / 1e3, 2),
                   "step_us": round(per_launch_us / (nq // TB), 3),
                   "slot_ns": round(per_launch_us * 1e3 / (nq * R), 2),
                   "host_launch_us": round(host_s / args.launches * 1e6, 1)}
            if (nq, pattern) in base:
                row["step_ratio"] = round(row["step_us"]
                                          / base[(nq, pattern)], 4)
            results.append(row)
            print(" ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    width = {"d": D} if args.dist == "f32" else {"m": PQ_M, "c": PQ_C}
    print(json.dumps({"device_kind": dev.device_kind, "dist": args.dist,
                      "n": N, **width, "r": R, "ef": EF, "cases": results}))
    if not all(r["bit_exact"] for r in results):
        sys.exit("hop_launch: a case differs from beam_hop_ref")


if __name__ == "__main__":
    main()
