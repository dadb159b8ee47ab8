"""Device time of one fused beam-hop launch at the ann-laion serve widths.

    python3 benchmarks/hop_launch.py [--q 128 512 1024] [--launches 20]

Runs ``beam_hop_pallas`` alone on a TPU over a 270,000 x 600 f32 table
(pre-padded, as the search hoists it), R=32 neighbour ids per node, ef=64
pools, for each batch size Q and two liveness patterns:

  * ``all``: every lane live, every neighbour slot valid;
  * ``cell``: the graph cell's liveness, 43% of lanes dead (``sel < 0``)
    and each node's row -1-padded past an out-degree drawn from 2..32
    (mean 17).

Each case is checked against ``beam_hop_ref`` bit for bit, warmed, then run
``--launches`` times under the profiler. One line per case gives the
kernel op's device time per launch, per grid step (Q / 8 queries) and per
candidate slot (Q x R), the device time of all the launch's ops, and the
share of slots that are live. The last
line is a JSON object with every case. Exits non-zero off a TPU.
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
SEED = 20231003


def hop_inputs(nq: int, pattern: str, table_rows: int):
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
    q = jax.random.normal(k[7], (nq, D), jnp.float32)
    return sel, nbr, pool_i, pool_d, pool_v, q


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--q", type=int, nargs="+", default=[128, 512, 1024])
    ap.add_argument("--launches", type=int, default=20)
    args = ap.parse_args()

    import jax
    import numpy as np
    from bench import trace
    from repro.kernels.beam_hop import beam_hop_pallas, beam_hop_ref
    from repro.kernels.row_gather import pad_table

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"hop_launch: needs a TPU, JAX's platform is "
                 f"{dev.platform!r}")
    db = jax.random.normal(jax.random.PRNGKey(SEED), (N, D))
    table = jax.block_until_ready(pad_table(db))
    results = []
    for nq in args.q:
        for pattern in ("all", "cell"):
            sel, nbr, pi, pd, pv, q = hop_inputs(nq, pattern, N)
            hop_args = (sel, nbr, pi, pd, pv, q, table)
            got = jax.block_until_ready(
                beam_hop_pallas(*hop_args, interpret=False))
            want = beam_hop_ref(*hop_args[:-1], db)
            exact = all(bool(np.array_equal(np.asarray(a), np.asarray(b)))
                        for a, b in zip(want, got))
            live = float(np.mean((np.asarray(nbr)[np.maximum(
                np.asarray(sel), 0)] >= 0) & (np.asarray(sel) >= 0)[:, None]))
            with tempfile.TemporaryDirectory() as tmp:
                t = time.perf_counter()
                with jax.profiler.trace(tmp):
                    for _ in range(args.launches):
                        out = beam_hop_pallas(*hop_args, interpret=False)
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
            row = {"q": nq, "pattern": pattern, "bit_exact": exact,
                   "live_share": round(live, 4), "launches": len(hop),
                   "launch_us": round(per_launch_us, 2),
                   "program_us": round(sum(map(sum, by_op.values()))
                                       / len(hop) / 1e3, 2),
                   "step_us": round(per_launch_us / (nq // TB), 3),
                   "slot_ns": round(per_launch_us * 1e3 / (nq * R), 2),
                   "host_launch_us": round(host_s / args.launches * 1e6, 1)}
            results.append(row)
            print(" ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    print(json.dumps({"device_kind": dev.device_kind, "n": N, "d": D,
                      "r": R, "ef": EF, "cases": results}))
    if not all(r["bit_exact"] for r in results):
        sys.exit("hop_launch: a case differs from beam_hop_ref")


if __name__ == "__main__":
    main()
