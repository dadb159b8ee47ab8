"""The whole command at a tiny size on the CPU (flat cell): a sound run is
correct, the control and the faults of the timed path are not, and a
run without a TPU, or without the program beside it, prints no result."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench_small

from bench import control, run  # noqa: E402

CELL = "laion300k-flat.bulk"
SEED = 2**31 + 101


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def _run(cell, seed, seconds=1.0, trace=False):
    return run.measure(run.prepare(cell), seed, seconds, trace)


def test_flat_run_is_correct(monkeypatch, tmp_path):
    bench_small.shrink(monkeypatch, tmp_path)
    out = _run(CELL, SEED)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    assert out["metrics"]["recall_at_10"]["value"] == 1.0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_flat_traced_run_reports_per_layer(monkeypatch, tmp_path):
    bench_small.shrink(monkeypatch, tmp_path)
    out = _run(CELL, SEED, trace=True)
    assert out["correct"], out["checks"]
    # the CPU trace has no device plane: device metrics are left out
    assert set(out["metrics"]) == {"flush_ms"}
    assert {"busy_s", "window_s"} <= set(out["device"])


def _break(monkeypatch, cls, fault):
    """Break ``cls.search`` where it produces its answers."""
    orig = cls.search

    def search(self, queries, k, *args, **kwargs):
        d, i = orig(self, queries, k, *args, **kwargs)
        if fault == "half_left_out":
            # the second half of the batch gets the first half's answers
            h = queries.shape[0] // 2
            d = np.concatenate([d[:queries.shape[0] - h], d[:h]])
            i = np.concatenate([i[:queries.shape[0] - h], i[:h]])
        elif fault == "answer_altered":
            i = np.array(i)
            i[0, 3] = (i[0, 3] + 1) % bench_small.N_ROWS
        return d, i

    monkeypatch.setattr(cls, "search", search)


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
def test_flat_faults_make_the_run_incorrect(monkeypatch, tmp_path, fault):
    from repro.core.flat import FlatIndex
    bench_small.shrink(monkeypatch, tmp_path)
    _break(monkeypatch, FlatIndex, fault)
    out = _run(CELL, SEED)
    assert not out["correct"], out["checks"]


def test_control_is_not_correct(monkeypatch, tmp_path):
    bench_small.shrink(monkeypatch, tmp_path)
    prog, = control.readings(CELL, [SEED], 1.0, control=False)
    ctrl, = control.readings(CELL, [SEED], 1.0, control=True)
    assert prog["correct"] and not ctrl["correct"], (prog["checks"],
                                                     ctrl["checks"])
    assert ctrl["checks"]["dist_err_max"]["value"] > \
        3 * prog["checks"]["dist_err_max"]["value"]


def _command(cwd, *extra):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", *extra], cwd=cwd, env=_env(),
        capture_output=True, text=True, timeout=300)


def _result_lines(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    return [json.loads(ln) for ln in lines]


def test_without_a_tpu_the_command_fails_with_no_result():
    p = _command(bench_small.ROOT)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_fail_with_no_result(tmp_path):
    shutil.copy(bench_small.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_small.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "sources are not" in p.stderr
