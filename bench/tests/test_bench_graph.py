"""The whole command at a tiny size on the CPU (graph cell): the first run
builds and snapshots the index, later runs restore it and build nothing,
and the control and the faults of the timed path make a run incorrect."""
import numpy as np
import pytest

import bench_small

from bench import control, run  # noqa: E402

CELL = "laion300k-nsg32-f32.bulk"
SEED = 2**31 + 303


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_cache")


def test_graph_run_builds_then_restores(monkeypatch, cache, capsys):
    bench_small.shrink(monkeypatch, cache)
    out = run.measure(run.prepare(CELL), SEED, 1.0, False)
    first = capsys.readouterr().out
    assert "stage seconds: antihub=" in first and "saved snapshot" in first
    assert out["correct"], out["checks"]
    assert out["metrics"]["recall_at_10"]["value"] >= 0.9

    out = run.measure(run.prepare(CELL), SEED + 1, 1.0, True)
    second = capsys.readouterr().out
    assert "restored snapshot" in second and "built nothing" in second
    assert "stage seconds" not in second
    assert out["correct"], out["checks"]
    assert {"hops_per_lane", "active_fraction", "flush_ms"} <= \
        set(out["metrics"])
    assert 0 < out["metrics"]["active_fraction"]["value"] <= 1


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
def test_graph_faults_make_the_run_incorrect(monkeypatch, cache, fault):
    from repro.core.pipeline import TunedGraphIndex
    bench_small.shrink(monkeypatch, cache)
    orig = TunedGraphIndex.search

    def search(self, queries, k, *args, **kwargs):
        d, i = orig(self, queries, k, *args, **kwargs)
        n = queries.shape[0]
        if fault == "half_left_out":
            h = n // 2
            d = np.concatenate([d[:n - h], d[:h]])
            i = np.concatenate([i[:n - h], i[:h]])
        else:
            i = np.array(i)
            i[0, 3] = (i[0, 3] + 1) % bench_small.N_ROWS
        return d, i

    monkeypatch.setattr(TunedGraphIndex, "search", search)
    out = run.measure(run.prepare(CELL), SEED, 1.0, False)
    assert not out["correct"], out["checks"]


def test_graph_control_is_not_correct(monkeypatch, cache):
    bench_small.shrink(monkeypatch, cache)
    prog, = control.readings(CELL, [SEED], 1.0, control=False)
    ctrl, = control.readings(CELL, [SEED], 1.0, control=True)
    assert prog["correct"] and not ctrl["correct"], (prog["checks"],
                                                     ctrl["checks"])
    assert ctrl["checks"]["dist_err_max"]["value"] > \
        3 * prog["checks"]["dist_err_max"]["value"]
