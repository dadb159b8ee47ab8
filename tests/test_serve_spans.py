"""The serve path's profiler spans and the search counters' read: the
flush span carries its arguments, padding is counted as the bucketed
search dispatches it, and ``search_stats`` reads the counters with one
copy and no program of its own."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.batching import MicroBatchQueue, pow2_buckets
from repro.serve.serve_step import ann_search_step
from repro.serve.spans import SPANS, span

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def _host_events(trace_dir: Path, name: str):
    from jax.profiler import ProfileData
    f = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    return [dict(ev.stats) for plane in ProfileData.from_file(str(f)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events if ev.name == name]


def test_flush_span_carries_index_rows_and_padding(ann_data, tmp_path):
    from repro.core.flat import FlatIndex
    step = ann_search_step(FlatIndex(ann_data["data"]), k=10,
                           buckets=pow2_buckets(8))
    queue = MicroBatchQueue(step, window_s=10.0)
    q = np.asarray(ann_data["queries"])
    queue.submit(q[:2])
    queue.flush()                                   # flush 0, untraced
    with jax.profiler.trace(str(tmp_path)):
        queue.submit(q[:3])
        queue.submit(q[3:5])
        queue.flush()                               # 5 rows -> bucket 8
        queue.submit(q[:11])
        queue.flush()                               # 11 rows -> 8 + 4
    got = _host_events(tmp_path, "queue.flush")
    assert got == [{"flush": 1, "rows": 5, "padded": 8},
                   {"flush": 2, "rows": 11, "padded": 12}]
    assert [e["bucket"] for e in _host_events(tmp_path, "search.call")] \
        == [8, 8, 4]
    h2d = _host_events(tmp_path, "queue.h2d")
    assert [e["bytes"] for e in h2d] == [5 * q[0].nbytes, 11 * q[0].nbytes]


@pytest.mark.parametrize("n", [1, 5, 8, 9, 16, 19])
def test_padded_size_is_what_the_search_dispatches(ann_data, n):
    from repro.core.flat import FlatIndex
    step = ann_search_step(FlatIndex(ann_data["data"]), k=10,
                           buckets=pow2_buckets(8))
    before = len(step.dispatched)
    step(ann_data["queries"][:n])
    assert step.padded_size(n) == sum(step.dispatched[before:])


def test_span_names_are_unique():
    assert len(SPANS) == len(set(SPANS))
    with span("queue.flush", flush=0, rows=1, padded=1):
        pass                                        # no profiler: a no-op


def _old_search_stats(s, r):
    """The counters as the search read them before: eager sums on the
    device (r: the graph's degree)."""
    hops = np.asarray(s.hops)
    total = int(hops.sum())
    wasted = int(jnp.sum(s.wasted_hops))
    gathered = int(jnp.sum(s.gathered))
    return {"hops": total, "gathered": gathered,
            "dup_gathered": int(jnp.sum(s.dup_gathered)),
            "wasted_hops": wasted,
            "active_fraction": float(total / max(total + wasted, 1)),
            "fetch_share": float(gathered / max(r * (total + wasted), 1)),
            "mean_hops": float(hops.mean()) if hops.size else 0.0,
            "p99_hops": float(np.percentile(hops, 99)) if hops.size else 0.0}


def test_search_stats_same_dict_one_copy_no_program(small_nsg, ann_data,
                                                    monkeypatch):
    """13 queries: a batch shape no other search-stats read has seen, so
    an eager device sum would compile here."""
    q = ann_data["queries"][:13]
    small_nsg.search(q, 10)
    small_nsg.search(q, 10)                         # the search is warm
    events, gets = [], []
    device_get = jax.device_get

    def counted_get(x):
        gets.append(x)
        return device_get(x)

    monkeypatch.setattr(jax, "device_get", counted_get)

    def on(name, *args, **kwargs):
        if name in COMPILE_EVENTS:
            events.append(name)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        first = small_nsg.search_stats()
        again = small_nsg.search_stats()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert events == [] and len(gets) == 2
    assert first == again == _old_search_stats(
        small_nsg.last_search_stats, small_nsg.graph.neighbors.shape[1])
    assert set(first) == {"hops", "gathered", "dup_gathered", "wasted_hops",
                          "active_fraction", "fetch_share", "mean_hops",
                          "p99_hops"}
    assert all(type(first[k]) is int for k in
               ("hops", "gathered", "dup_gathered", "wasted_hops"))


def _host_spans(trace_dir: Path):
    """(name, start_ns, end_ns) of every host event of the newest trace."""
    from jax.profiler import ProfileData
    f = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(str(f)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.fixture(scope="module")
def small_pq_nsg(ann_data):
    from repro.core import build_vanilla_nsg
    return build_vanilla_nsg(ann_data["data"], degree=12, ef_search=48,
                             build_knn_k=12, build_candidates=32,
                             dist_backend="pq", rerank=32)


@pytest.mark.parametrize("dist", ["pq", "f32"])
def test_quantized_search_spans_its_table_and_rerank(small_pq_nsg, ann_data,
                                                     tmp_path, dist):
    """A PQ search opens ``search.lut`` and then ``search.rerank``, both
    inside ``search.traverse``; an f32 search over the same index opens
    neither."""
    q = ann_data["queries"][:9]
    small_pq_nsg.search(q, 10, dist_backend=dist)   # compiled outside
    with jax.profiler.trace(str(tmp_path)):
        small_pq_nsg.search(q, 10, dist_backend=dist)
    events = _host_spans(tmp_path)
    by_name = {}
    for name, s, e in events:
        by_name.setdefault(name, []).append((s, e))
    traverse, = by_name["search.traverse"]
    if dist == "f32":
        assert "search.lut" not in by_name and "search.rerank" not in by_name
        return
    (lut,), (rerank,) = by_name["search.lut"], by_name["search.rerank"]
    for s, e in (lut, rerank):
        assert traverse[0] <= s <= e <= traverse[1]
    assert lut[1] <= rerank[0]


def test_spans_name_the_quantized_search_stages():
    assert {"search.lut", "search.rerank"} <= set(SPANS)
    assert SPANS.index("search.traverse") < SPANS.index("search.lut") \
        < SPANS.index("search.rerank") < SPANS.index("search.ids")
