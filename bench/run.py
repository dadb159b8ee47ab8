"""Run one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the configuration's corpus on the device from its fixed seed,
opens the system under test (``bench/systems.py``: restore a snapshot, or
build one), makes the run's query sets from ``--seed`` and pushes one
request of every size the mix sends through the serve queue, so that
nothing compiles in the window. The window (``bench/serve_loop.py``) then
drives the program's ``MicroBatchQueue`` for ``--seconds``. After it, the
device's peak memory is read, the program's state is freed, and a sample
of the answers is compared with the plain reference (``bench/check.py``).

With ``--trace 1`` the window runs under the JAX profiler, the program's
search counters are read after every flush, and the per-layer metrics
replace the end-to-end ones. The last line of standard output is one JSON
object; the numbers compared for ``correct`` end standard error.

The run exits non-zero, with no result line, when JAX's devices are not
TPUs or fewer than the cell asks for, or when the program's sources are not
beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

SRC = ROOT / "src"
CACHE = ROOT / "bench" / "cache"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def log(msg: str) -> None:
    """A line of standard output, stamped with seconds since start."""
    print(f"[{time.perf_counter() - T_START:8.3f}s] {msg}", flush=True)


def require_devices(chips: int):
    """The TPU devices this cell needs; exits when there are not enough."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX's platform is "
                 f"{devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees "
                 f"{len(devices)}")
    return devices


def enable_compile_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout,
    keeping every program, so that only a cell's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts JAX traces and backend compiles as they happen."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, *args, **kwargs) -> None:
        if name in COMPILE_EVENTS:
            self.n += 1


@dataclass
class Cell:
    """A cell's set-up, shared by every seed measured in one process."""
    name: str
    entry: dict
    config: dict
    mix: dict
    bench: dict
    devices: list
    corpus: object
    index: object
    step: object
    compiles: CompileCounter


def prepare(name: str, index_factory: Optional[Callable] = None) -> Cell:
    """Corpus, system under test and serve step of cell ``name``.

    ``index_factory(corpus)``, where given, stands in for the system the
    configuration names (the control does this).
    """
    bench = spec.benchmark()
    entry = spec.workload(bench, name)
    cfg, cfg_bytes = spec.config(bench, entry["config"])
    mix = spec.traffic(entry["traffic"])
    if not (SRC / "repro").is_dir():
        sys.exit(f"bench: the program's sources are not at {SRC}")
    import jax
    log("jax imported")
    enable_compile_cache()
    devices = require_devices(entry["chips"])
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    logging.basicConfig(format="%(message)s", stream=sys.stdout)
    logging.getLogger("repro").setLevel(logging.INFO)
    compiles = CompileCounter()

    from bench import data, systems
    from repro.core import SearchParams
    from repro.serve.batching import pow2_buckets
    from repro.serve.serve_step import ann_search_step

    t = time.perf_counter()
    corpus = jax.block_until_ready(data.corpus(cfg["corpus"]))
    log(f"corpus: {tuple(corpus.shape)} from seed {cfg['corpus']['seed']} "
        f"in {time.perf_counter() - t:.3f}s")
    if index_factory is None:
        index, info = systems.open_index(cfg, cfg_bytes, corpus, SRC, CACHE,
                                         log)
    else:
        index, info = index_factory(corpus), {"kind": "stand-in"}
    log(f"index: {info}")
    step = ann_search_step(index, cfg["k"], SearchParams(**cfg["search"]),
                           buckets=pow2_buckets(cfg["max_batch"]))
    return Cell(name, entry, cfg, mix, bench, devices, corpus, index, step,
                compiles)


def _served_shape(index) -> dict:
    base = getattr(index, "base", None)
    if base is None:
        base = getattr(index, "data", None)
    graph = getattr(index, "graph", None)
    return {"rows": int(base.shape[0]), "dim": int(base.shape[1]),
            "degree": int(graph.neighbors.shape[1]) if graph else 0}


def _span(tracing: bool):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _warm(cell: Cell, schedule, sets, read_stats) -> None:
    """One request of every size the mix sends, through the queue."""
    from repro.serve.batching import MicroBatchQueue
    queue = MicroBatchQueue(cell.step, window_s=0.0)
    for rows in sorted({n for _, n in schedule.per_set}):
        ticket = queue.submit(sets[0][:rows])
        queue.flush()
        queue.take(ticket)
        if read_stats:
            read_stats()


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *,
            free: bool = True, t_start: float = T_START) -> dict:
    """One measured window on ``cell`` and its result line's object."""
    import jax
    from bench import check, serve_loop, traffic
    from bench import trace as bench_trace
    from bench.record import RunRecord
    from repro.serve.batching import MicroBatchQueue

    mix = cell.mix
    sets = traffic.query_sets(mix, cell.corpus, seed)
    schedule = traffic.Schedule(mix, seed, seconds)
    read_stats = getattr(cell.step, "search_stats", None) if trace else None
    log(f"query sets: {len(sets)} x {sets[0].shape}")
    _warm(cell, schedule, sets, read_stats)
    log(f"warm-up done; {cell.compiles.n} traces and compiles in set-up")
    shape = _served_shape(cell.index)
    queue = MicroBatchQueue(cell.step, window_s=mix["batch_window_s"])
    trace_dir = CACHE / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    span = _span(trace)
    setup_s = time.perf_counter() - t_start
    with span("window"):
        win = serve_loop.run(queue, schedule, sets, seconds, span=span,
                             read_stats=read_stats,
                             compiles=lambda: cell.compiles.n)
    if trace:
        jax.profiler.stop_trace()
    log(f"window: {win.seconds:.3f}s, {len(win.flushes)} flushes, "
        f"{win.answered} queries answered, {win.compiles} compilations")
    memory_peak = peak_bytes(cell.devices[:cell.entry["chips"]])
    if free:
        cell.index = cell.step = queue = read_stats = None
        gc.collect()
    answers = check.gather(win.done, cell.config["k"])
    checks, readings = check.compare(
        answers, sets, cell.corpus, cell.config["k"],
        cell.config["guarantees"], mix["check_sample"], seed)
    summary = None
    if trace:
        summary = bench_trace.summarize(*bench_trace.load(trace_dir))
    dev = cell.devices[0]
    rec = RunRecord(cell.entry, cell.config, mix, setup_s, win, readings,
                    dev.device_kind, shape, summary)
    metrics = {}
    for m in spec.metrics_for(cell.bench, cell.name, trace):
        value = spec.metric(m["name"]).reduce(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": memory_peak}
    out = {"correct": check.holds(checks), "attempted": win.attempted,
           "failed": answers.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s if summary else 0.0
        device["window_s"] = summary.window_s if summary else win.seconds
        if summary:
            out["breakdown"] = {
                "device_ops": [[k, v] for k, v in summary.top_ops.items()],
                "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    out["checks"] = checks
    return out


def report(out: dict) -> None:
    for name, c in out["checks"].items():
        side = ">=" if name.endswith("_min") else "<="
        print(f"check {name}: {c['value']!r} {side} {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = prepare(args.workload)
    report(measure(cell, args.seed, args.seconds, bool(args.trace)))


if __name__ == "__main__":
    main()
