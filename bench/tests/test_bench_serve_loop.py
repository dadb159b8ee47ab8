"""The window: closes at the end of the first flush that ends after the
given seconds, and counts whole flushes only."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import serve_loop, traffic  # noqa: E402


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class SlowSearch:
    """A bucketed search that advances the fake clock per call."""

    def __init__(self, clock, seconds_per_call, max_batch=1024):
        self.clock, self.dt = clock, seconds_per_call
        self.dispatched = []
        self.max_batch = max_batch

    def __call__(self, q):
        self.clock.t += self.dt
        self.dispatched.append(self.max_batch)
        n = q.shape[0]
        return np.zeros((n, 10), np.float32), np.tile(np.arange(10), (n, 1))


def _run(seconds, dt, mix):
    from repro.serve.batching import MicroBatchQueue
    clock = Clock()
    search = SlowSearch(clock, dt)
    queue = MicroBatchQueue(search, window_s=mix["batch_window_s"])
    sets = [np.zeros((mix["set_size"], 4), np.float32)] * mix["query_sets"]
    sched = traffic.Schedule(mix, seed=0, seconds=seconds)
    return serve_loop.run(queue, sched, sets, seconds, clock=clock)


def test_window_closes_on_a_flush_boundary():
    mix = dict(set_size=10000, request_rows={"kind": "fixed", "rows": 1024},
               query_sets=2, arrival="closed", batch_window_s=0.0)
    win = _run(seconds=10.0, dt=3.0, mix=mix)
    # flushes end at 3, 6, 9, 12: the first to end after 10 s closes it
    assert len(win.flushes) == 4
    assert win.seconds == pytest.approx(12.0)
    assert win.answered == 4 * 1024
    assert win.attempted == 4 * 1024
    assert win.answered / win.seconds == pytest.approx(4 * 1024 / 12.0)
    assert [f.end for f in win.flushes] == pytest.approx([3, 6, 9, 12])
    assert all(f.padded == 1024 for f in win.flushes)
    assert len(win.done) == 4


def test_qps_counts_the_short_request_of_a_set():
    mix = dict(set_size=1500, request_rows={"kind": "fixed", "rows": 1024},
               query_sets=1, arrival="closed", batch_window_s=0.0)
    win = _run(seconds=3.5, dt=1.0, mix=mix)
    # requests of 1024, 476, 1024, 476 rows end at 1, 2, 3, 4 s
    assert win.seconds == pytest.approx(4.0)
    assert win.answered == 2 * 1500
    assert [f.rows for f in win.flushes] == [1024, 476, 1024, 476]


class TickingClock(Clock):
    """Advances a millisecond per read, so an open loop reaches its due
    times without sleeping through them."""

    def __call__(self):
        self.t += 0.001
        return self.t


def test_open_loop_submits_when_due_and_times_from_due():
    from repro.serve.batching import MicroBatchQueue
    mix = dict(set_size=200, request_rows={"kind": "geometric", "p": 0.5,
                                           "max": 16},
               query_sets=1, arrival="poisson", rate_rps=400.0,
               batch_window_s=0.002)
    clock = TickingClock()
    search = SlowSearch(clock, 0.003, max_batch=64)
    queue = MicroBatchQueue(search, window_s=mix["batch_window_s"])
    sched = traffic.Schedule(mix, seed=5, seconds=1.0)
    sets = [np.zeros((200, 4), np.float32)]
    win = serve_loop.run(queue, sched, sets, 1.0, clock=clock)
    assert win.seconds >= 1.0
    assert len(win.done) == len(win.latency_s) == len(win.late_s)
    assert win.attempted == sum(r.rows for r, _ in win.done)
    # each request waits at least its queue window past its due time
    assert min(win.late_s) >= 0
    assert min(win.latency_s) >= 0.003
    assert len(win.flushes) > 1 and all(f.rows <= 64 for f in win.flushes)


def test_window_reducers():
    from types import SimpleNamespace

    from bench import spec
    from bench.serve_loop import Flush, Window
    stats = {"hops": 300, "wasted_hops": 100, "gathered": 9000}
    latency = [0.01 * i for i in range(1, 101)]
    win = Window(seconds=2.0, answered=3000, latency_s=latency,
                 flushes=[Flush(0, 0.5, 1000, 1024, stats),
                          Flush(0.5, 1.5, 1000, 1024, stats),
                          Flush(1.5, 2.0, 1000, 1024, stats)])
    rec = SimpleNamespace(window=win, setup_s=7.0, trace=None,
                          readings={"recall_at_10": 0.95, "sampled": 10})
    value = {m: spec.metric(m).reduce(rec) for m in (
        "qps", "recall_at_10", "setup_s", "flush_ms",
        "hops_per_lane", "active_fraction", "device_idle_share",
        "beam_hop_roofline.f32", "flat_scan_roofline")}
    assert value["qps"] == pytest.approx(1500.0)
    assert value["recall_at_10"] == 0.95 and value["setup_s"] == 7.0
    assert value["flush_ms"] == pytest.approx(500.0)
    assert value["hops_per_lane"] == pytest.approx(900 / 3072)
    assert value["active_fraction"] == pytest.approx(0.75)
    # without a trace the device metrics have nothing to read
    assert value["device_idle_share"] is None
    assert value["beam_hop_roofline.f32"] is None
    assert value["flat_scan_roofline"] is None
