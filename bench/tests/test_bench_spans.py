"""The program's serve-path spans as the benchmark reads them: recorded
around a real flush, nested as ``repro.serve.spans`` says; idle device
time split by them; and the existing reducers unchanged on the recorded
chip trace."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import spans, spec, trace  # noqa: E402
from bench.record import RunRecord  # noqa: E402
from bench.serve_loop import Flush, Window  # noqa: E402
from bench.trace import Event, Span  # noqa: E402

MS = 1e6   # ns
DEV = "/device:TPU:0"
DATA = Path(__file__).parent / "data"
# the flat cell's 0.275 s window on a TPU v5 lite, with the program's spans
SPANS_DATA = Path(__file__).parent / "data_spans"

# each span's parent, as repro/serve/spans.py documents the nesting
PARENT = {"queue.h2d": "queue.flush", "search.call": "queue.flush",
          "bucket.pad": "search.call", "index.search": "search.call",
          "search.project": "index.search", "search.entries": "index.search",
          "search.traverse": "index.search", "search.ids": "index.search",
          "search.scan": "index.search", "bucket.slice": "search.call",
          "queue.d2h": "queue.flush", "queue.scatter": "queue.flush"}
STAGES = {"graph": ("search.project", "search.entries", "search.traverse",
                    "search.ids"),
          "flat": ("search.scan",)}


def _index(kind):
    import jax
    from repro.core import FlatIndex, build_vanilla_nsg
    from repro.data import clustered_vectors
    data = clustered_vectors(jax.random.PRNGKey(3), 600, 16, n_clusters=6)
    if kind == "flat":
        return FlatIndex(data), data
    return build_vanilla_nsg(data, degree=8, ef_search=16, build_knn_k=8,
                             build_candidates=16), data


def _serve(queue, rows):
    ticket = queue.submit(np.asarray(rows))
    queue.flush()
    return queue.take(ticket)


@pytest.mark.parametrize("kind", ["graph", "flat"])
def test_flush_spans_nest(kind, tmp_path):
    """One padded flush through the queue, recorded by the profiler:
    every span of the table is there once, inside its parent."""
    import jax
    from repro.serve.batching import MicroBatchQueue, pow2_buckets
    from repro.serve.serve_step import ann_search_step
    index, data = _index(kind)
    step = ann_search_step(index, 5, buckets=pow2_buckets(16))
    queue = MicroBatchQueue(step, window_s=0.0)
    _serve(queue, data[:3])                             # compile, untraced
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            ticket = queue.submit(np.asarray(data[:11]))  # 11 -> bucket 16
            queue.flush()
            step.search_stats()
    assert queue.take(ticket)[1].shape == (11, 5)
    got = spans.load(tmp_path, spans.all_names())
    by_name = {}
    for sp in got:
        by_name.setdefault(sp.name, []).append(sp)
    want = {"queue.flush", "queue.h2d", "search.call", "bucket.pad",
            "index.search", "bucket.slice", "queue.d2h",
            "queue.scatter"} | set(STAGES[kind])
    if kind == "graph":
        want.add("index.stats")
    assert want <= set(by_name)
    assert not set(by_name) & (set(STAGES["graph"] + STAGES["flat"])
                               - set(STAGES[kind]))
    for name in want:
        assert len(by_name[name]) == 1, name
    for child, parent in PARENT.items():
        if child in want:
            c, p = by_name[child][0], by_name[parent][0]
            assert p.start_ns <= c.start_ns and c.end_ns <= p.end_ns, child
    flush = by_name["queue.flush"][0]
    if kind == "graph":
        stats = by_name["index.stats"][0]
        assert stats.start_ns >= flush.end_ns


def test_every_span_the_program_opens_is_named_in_spans():
    """A span name used in ``src/repro`` and missing from ``SPANS`` would
    never be read back."""
    from repro.serve.spans import SPANS
    used = set()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        used |= set(re.findall(r"\bspan\(\"([^\"]+)\"", path.read_text()))
    assert used == set(SPANS)


# --- idle time split by spans, on synthetic events ------------------------

def _ops():
    return [Event(DEV, "%fusion.1", 10 * MS, 20 * MS, "jit_l2_topk"),
            Event(DEV, "%fusion.2", 60 * MS, 10 * MS, "jit_l2_topk"),
            Event(DEV, "%fusion.3", 80 * MS, 10 * MS, "jit_l2_topk")]


def _spans():
    # window [5, 105]; idle [5,10] [30,60] [70,80] [90,105]
    return [Span("window", 5 * MS, 105 * MS),
            Span("flush", 6 * MS, 75 * MS),
            Span("queue.flush", 6 * MS, 75 * MS),
            Span("queue.h2d", 6 * MS, 9 * MS),
            Span("search.call", 9 * MS, 12 * MS),
            Span("queue.d2h", 12 * MS, 50 * MS),
            Span("search.call", 50 * MS, 65 * MS),
            Span("bucket.pad", 51 * MS, 59 * MS),
            Span("backend_compile_and_load", 52 * MS, 58 * MS),
            Span("queue.scatter", 65 * MS, 75 * MS),
            Span("submit", 76 * MS, 78 * MS)]


def test_gap_inside_d2h_inside_flush_is_named_d2h():
    att = spans.attribute(_ops(), _spans())
    gaps = att.gaps()
    # [30, 60] has its midpoint 45 in queue.d2h, not in the harness's flush
    assert gaps[0] == ("queue.d2h", pytest.approx(0.030))
    assert ("queue.scatter", pytest.approx(0.010)) in gaps   # [70, 80]
    assert ("between spans", pytest.approx(0.015)) in gaps   # [90, 105]


def test_compile_inside_pad_names_its_gap():
    ops = [Event(DEV, "%a", 5 * MS, 50 * MS), Event(DEV, "%b", 59 * MS,
                                                     46 * MS)]
    att = spans.attribute(ops, _spans())
    assert att.gaps() == [("backend_compile_and_load",
                           pytest.approx(0.004))]


def test_idle_by_span_and_the_shares_add_up():
    att = spans.attribute(_ops(), _spans())
    summary = trace.summarize(_ops(), [], _spans())
    share = 100.0 * (1 - summary.busy_s / summary.window_s)
    assert att.idle_s() == pytest.approx(summary.window_s - summary.busy_s)
    by = att.by_span()
    assert by["queue.d2h"] == pytest.approx(0.020)        # [30, 50]
    assert by["search.call"] == pytest.approx(0.001 + 0.010)  # [9,10] [50,60]
    assert by["bucket.pad"] == pytest.approx(0.008)       # [51, 59]
    assert by["queue.h2d"] == pytest.approx(0.003)        # [6, 9]
    assert by["flush"] == pytest.approx(0.004 + 0.030 + 0.005)
    assert "window" not in by
    dispatch = att.share(spans.DISPATCH)
    transfer = att.share(spans.TRANSFER)
    assert dispatch == pytest.approx(11.0)
    assert transfer == pytest.approx(23.0)
    assert dispatch + transfer <= share
    assert share == pytest.approx(60.0)


def test_idle_averages_over_devices_as_busy_does():
    ops = [Event("/device:TPU:0", "a", 10 * MS, 50 * MS),
           Event("/device:TPU:1", "a", 10 * MS, 10 * MS)]
    sp = [Span("window", 0, 100 * MS), Span("search.call", 0, 40 * MS)]
    att = spans.attribute(ops, sp)
    summary = trace.summarize(ops, [], sp)
    assert att.idle_s() == pytest.approx(summary.window_s - summary.busy_s)
    # idle under search.call: device 0 [0,10], device 1 [0,10] + [20,40]
    assert att.idle_s(spans.DISPATCH) == pytest.approx(0.020)


def test_no_window_or_no_op_gives_none():
    assert spans.attribute(_ops(), _spans()[1:]) is None
    assert spans.attribute([], _spans()) is None


# --- the reducers on a run record -----------------------------------------

def _record(summary, flushes=(), kind="TPU v5 lite", rows=300_000):
    win = Window(seconds=summary.window_s if summary else 1.0,
                 flushes=list(flushes))
    return RunRecord({}, {}, {}, 0.0, win, {}, kind,
                     {"rows": rows, "dim": 768, "degree": 0}, summary)


def test_metrics_read_a_recorded_trace(monkeypatch, tmp_path):
    """A trace with the program's spans, recorded here around a real
    flush (the CPU has no device plane, so the device ops are placed
    around the recorded spans): both metrics read it, their sum stays
    within ``device_idle_share``, and the breakdown's gaps are named by
    the program's spans."""
    import jax
    from bench import run as bench_run
    from repro.serve.batching import MicroBatchQueue, pow2_buckets
    from repro.serve.serve_step import ann_search_step
    index, data = _index("flat")
    queue = MicroBatchQueue(ann_search_step(index, 5,
                                            buckets=pow2_buckets(16)),
                            window_s=0.0)
    _serve(queue, data[:16])
    ticket = queue.submit(np.asarray(data[:16]))
    monkeypatch.setattr(bench_run, "CACHE", tmp_path)
    with jax.profiler.trace(str(tmp_path / "trace")):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("flush"):
                queue.flush()
    queue.take(ticket)
    got = {sp.name: sp for sp in spans.load(tmp_path / "trace",
                                             spans.all_names())}
    w, scan = got["window"], got["search.scan"]
    # the device runs the scan's second half and nothing else
    mid = (scan.start_ns + scan.end_ns) / 2
    ops = [Event(DEV, "%fusion.1", mid, scan.end_ns - mid, "jit_l2_topk")]
    summary = trace.summarize(ops, [], [w, got["flush"]])
    assert len(summary.idle_gaps) == 2
    rec = _record(summary)
    idle = spec.metric("device_idle_share").reduce(rec)
    dispatch = spec.metric("idle_in_dispatch").reduce(rec)
    transfer = spec.metric("idle_in_transfer").reduce(rec)
    assert 0 < dispatch and 0 < transfer
    assert dispatch + transfer <= idle + 1e-9
    # the gaps before and after the scan's second half
    names = [n for n, _ in summary.idle_gaps]
    assert "flush" not in names and len(names) == 2
    assert set(names) <= set(spans.program_spans())


def test_metrics_leave_themselves_out_without_program_spans(monkeypatch):
    """The recorded chip trace predates the program's spans (an older
    tree): both metrics give None, and the gaps keep their names."""
    ops, modules, sp = trace.load(DATA)
    summary = trace.summarize(ops, modules, sp)
    before = list(summary.idle_gaps)
    monkeypatch.setattr(spans, "trace_dir", lambda: DATA)
    rec = _record(summary)
    assert spec.metric("idle_in_dispatch").reduce(rec) is None
    assert spec.metric("idle_in_transfer").reduce(rec) is None
    assert summary.idle_gaps == before
    assert spec.metric("idle_in_dispatch").reduce(_record(None)) is None


def test_metrics_need_the_summarys_own_window(monkeypatch, tmp_path):
    """A stale trace whose window is not the summary's is not read."""
    ops, modules, sp = trace.load(DATA)
    summary = trace.summarize(ops, modules, sp)
    summary.window_s += 1.0
    monkeypatch.setattr(spans, "trace_dir", lambda: DATA)
    monkeypatch.setattr(spans, "program_spans", lambda: ("flush",))
    assert spans.for_run(_record(summary)) is None


def test_existing_reducers_read_the_recorded_trace_as_before():
    """The flat cell's 0.25 s chip window (6 flushes of 1,024 rows): the
    accepted reducers' values, pinned."""
    ops, modules, sp = trace.load(DATA)
    summary = trace.summarize(ops, modules, sp)
    w0 = next(s for s in sp if s.name == "window").start_ns
    flushes = [Flush((s.start_ns - w0) / 1e9, (s.end_ns - w0) / 1e9,
                     1024, 1024) for s in sp if s.name == "flush"]
    rec = _record(summary, flushes)
    assert len(flushes) == 6
    got = {m: spec.metric(m).reduce(rec)
           for m in ("device_idle_share", "flat_scan_roofline", "flush_ms",
                     "beam_hop_roofline.f32", "hops_per_lane",
                     "active_fraction")}
    assert got["device_idle_share"] == pytest.approx(9.144325324162883,
                                                     rel=1e-12)
    assert got["flat_scan_roofline"] == pytest.approx(5.68361142377562,
                                                      rel=1e-12)
    assert got["flush_ms"] == pytest.approx(45.75441450000001, rel=1e-12)
    assert got["beam_hop_roofline.f32"] is None
    assert got["hops_per_lane"] is None and got["active_fraction"] is None
    assert summary.idle_gaps[0] == ("flush", pytest.approx(0.004422))


def test_recorded_chip_trace_with_spans(monkeypatch):
    """The flat cell on a TPU v5 lite with the program's spans (6 flushes):
    the values its traced run printed, the split within the idle share,
    and every gap named by a span of the program."""
    ops, modules, sp = trace.load(SPANS_DATA)
    summary = trace.summarize(ops, modules, sp)
    monkeypatch.setattr(spans, "trace_dir", lambda: SPANS_DATA)
    rec = _record(summary)
    idle = spec.metric("device_idle_share").reduce(rec)
    dispatch = spec.metric("idle_in_dispatch").reduce(rec)
    transfer = spec.metric("idle_in_transfer").reduce(rec)
    assert idle == pytest.approx(8.121625573360857, rel=1e-12)
    assert dispatch == pytest.approx(0.5105029658510302, rel=1e-12)
    assert transfer == pytest.approx(7.252553425977749, rel=1e-12)
    assert dispatch + transfer <= idle
    assert summary.idle_gaps[:2] == [
        ("queue.h2d", pytest.approx(0.004365954)),
        ("queue.d2h", pytest.approx(0.003384569))]
    assert {n for n, _ in summary.idle_gaps} <= set(spans.program_spans())
    by = spans.for_run(rec).by_span()
    assert by["queue.d2h"] == pytest.approx(0.012124931)
    assert by["queue.h2d"] == pytest.approx(0.00783425)
    assert by["search.call"] == pytest.approx(0.001404915)


def test_command_line_prints_the_split():
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "spans.py"), str(DATA)],
        capture_output=True, text=True, check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["idle_s"] == pytest.approx(
        res["window_s"] * 9.144325324162883 / 100)
    assert res["idle_by_span"]["flush"] <= res["idle_s"]
    assert res["idle_in_dispatch"] == res["idle_in_transfer"] == 0.0
    assert res["idle_gaps"][0] == ["flush", pytest.approx(0.004422)]
