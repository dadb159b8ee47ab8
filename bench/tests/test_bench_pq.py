"""The quantized graph cell at a tiny size on the CPU, at its widths (PCA
600, PQ300x8, the fused hop in interpret mode): a run is correct and its
snapshot restores with the codes, a search that skips the exact rerank is
not correct, and the cell's two per-layer reducers read a synthetic
record."""
import numpy as np
import pytest

import bench_small

from bench import run, spec  # noqa: E402
from bench.record import RunRecord  # noqa: E402
from bench.serve_loop import Flush, Window  # noqa: E402
from bench.trace import Event, Span, summarize  # noqa: E402

CELL = "laion300k-nsg32-pq300.bulk"
SEED = 2**31 + 505
DEV = "/device:TPU:0"
MS = 1e6   # ns


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_cache")


def test_pq_run_builds_then_restores_and_is_correct(monkeypatch, cache,
                                                    capsys):
    bench_small.shrink(monkeypatch, cache)
    cell = run.prepare(CELL)
    assert cell.index.codec_backend == "pq"
    assert cell.index.codes.shape[1] == 300
    assert cell.index.base.shape[1] == 600
    out = run.measure(cell, SEED, 1.0, False)
    first = capsys.readouterr().out
    assert "quantize=" in first and "saved snapshot" in first
    assert out["correct"], out["checks"]
    assert out["metrics"]["recall_at_10"]["value"] >= 0.9

    cell = run.prepare(CELL)
    second = capsys.readouterr().out
    assert "restored snapshot" in second and "built nothing" in second
    assert cell.index.codec_backend == "pq"
    out = run.measure(cell, SEED + 1, 1.0, True)
    assert out["correct"], out["checks"]


def test_pq_without_the_rerank_is_not_correct(monkeypatch, cache):
    """Served with rerank=0 the answers carry the traversal's ADC
    distances, which the distance check refuses."""
    from repro.core.pipeline import TunedGraphIndex
    bench_small.shrink(monkeypatch, cache)
    orig = TunedGraphIndex.search

    def search(self, queries, k, *args, **kwargs):
        return orig(self, queries, k, *args, **dict(kwargs, rerank=0))

    monkeypatch.setattr(TunedGraphIndex, "search", search)
    out = run.measure(run.prepare(CELL), SEED, 1.0, False)
    assert not out["correct"], out["checks"]
    dist = out["checks"]["dist_err_max"]
    assert dist["value"] > dist["limit"]


# --- the cell's reducers on a synthetic record -----------------------------

def _record(ops, modules, flushes, pq_m=300):
    summary = summarize(ops, modules, [Span("window", 0, 100 * MS)])
    cfg = {"index": {"ann_config": {"pq_m": pq_m}}}
    win = Window(seconds=0.1, flushes=flushes)
    return RunRecord({}, cfg, {}, 0.0, win, {}, "TPU v5 lite",
                     {"rows": 270_000, "dim": 600, "degree": 32}, summary)


def _events():
    ops = [Event(DEV, "%fusion.1", 0, 5 * MS, "jit_pq_lut"),
           Event(DEV, "%beam_hop_pallas.2", 10 * MS, 60 * MS,
                 "jit_beam_search"),
           Event(DEV, "%fusion.9", 70 * MS, 10 * MS, "jit_beam_search"),
           Event(DEV, "%fusion.3", 85 * MS, 5 * MS, "jit__exact_rerank")]
    modules = [Event(DEV, "jit_pq_lut", 0, 5 * MS),
               Event(DEV, "jit_beam_search", 10 * MS, 70 * MS),
               Event(DEV, "jit__exact_rerank", 85 * MS, 5 * MS)]
    return ops, modules


def test_pq_hop_work_counts_ids_codes_and_one_table_per_lane():
    mod = spec.metric("beam_hop_roofline.pq")
    flop, byte = mod.pq_hop_work(lane_hops=10, gathered=300, lanes=4,
                                 degree=32, m=300)
    assert byte == 10 * 32 * 4 + 300 * 300 + 4 * 300 * 256 * 4
    assert flop == 300 * 300


def test_pq_reducers_read_a_synthetic_record():
    ops, modules = _events()
    stats = {"hops": 68_000, "gathered": 1_140_000}
    flushes = [Flush(0, 0.05, 1000, 1024, stats),
               Flush(0.05, 0.1, 1000, 1024, stats)]
    rec = _record(ops, modules, flushes)
    roof = spec.metric("beam_hop_roofline.pq").reduce(rec)
    byte = 2 * (68_000 * 32 * 4 + 1_140_000 * 300 + 1024 * 300 * 256 * 4)
    # memory-bound: the bytes at 819 GB/s over the hop's 60 ms
    assert roof == pytest.approx(100 * byte / 819e9 / 0.060)
    tail = spec.metric("pq_tail_share").reduce(rec)
    # 5 + 5 ms of the 80 ms busy (5 + 60 + 10 + 5)
    assert tail == pytest.approx(100 * 10 / 80)


def test_pq_reducers_without_their_inputs_give_none():
    ops, modules = _events()
    stats = {"hops": 1, "gathered": 1}
    flushes = [Flush(0, 0.1, 10, 16, stats)]
    roof, tail = (spec.metric(m) for m in
                  ("beam_hop_roofline.pq", "pq_tail_share"))
    no_trace = _record(ops, modules, flushes)
    no_trace.trace = None
    assert roof.reduce(no_trace) is None and tail.reduce(no_trace) is None
    # a configuration with no pq_m, and a window that ran no table build
    # and no rerank (the f32 path's programs only)
    f32 = _record(ops[1:3], modules[1:2], flushes)
    f32.config = {}
    assert roof.reduce(f32) is None
    assert tail.reduce(f32) is None
    assert roof.reduce(_record(ops, modules, [Flush(0, 0.1, 10, 16)])) \
        is None
    assert np.isfinite(tail.reduce(_record(ops, modules, flushes)))
