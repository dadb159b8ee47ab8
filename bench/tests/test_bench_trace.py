"""The trace reducer: busy and idle time, kernel time by pattern, and the
idle gaps named by the harness span open at the time."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402
from bench.trace import Event, Span  # noqa: E402

MS = 1e6   # ns


DEV = "/device:TPU:0"


def _events():
    return [
        Event(DEV, "%fusion.1", 10 * MS, 20 * MS, "jit_l2_topk"),
        Event(DEV, "%beam_hop_pallas.2", 25 * MS, 15 * MS, "jit_beam"),
        Event(DEV, "%fusion.2", 60 * MS, 10 * MS, "jit_l2_topk"),
        Event(DEV, "%while.3", 10 * MS, 30 * MS, "jit_l2_topk"),
        Event(DEV, "%before", 0, 5 * MS, "x"),          # before the window
    ]


def _modules():
    return [Event(DEV, "jit_l2_topk", 9 * MS, 32 * MS),
            Event(DEV, "jit_l2_topk", 59 * MS, 12 * MS),
            Event(DEV, "jit_beam", 24 * MS, 17 * MS)]


def _spans():
    return [Span("window", 5 * MS, 105 * MS),
            Span("flush", 8 * MS, 45 * MS),
            Span("take", 45 * MS, 52 * MS),
            Span("flush", 55 * MS, 75 * MS),
            Span("submit", 80 * MS, 100 * MS)]


def test_busy_is_the_union_inside_the_window():
    s = trace.summarize(_events(), _modules(), _spans())
    # union of [10, 40] and [60, 70] inside [5, 105]
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.040)
    assert not any("before" in k or "while" in k for k in s.top_ops)
    assert s.top_ops["%fusion.1 [jit_l2_topk]"] == pytest.approx(0.020)


def test_idle_gaps_named_by_the_open_span():
    s = trace.summarize(_events(), _modules(), _spans())
    # gaps: [5,10] mid 7.5 before any span, [40,60] mid 50 in take,
    # [70,105] mid 87.5 in submit
    assert s.idle_gaps[0] == ("submit", pytest.approx(0.035))
    assert s.idle_gaps[1] == ("take", pytest.approx(0.020))
    assert s.idle_gaps[2] == ("between spans", pytest.approx(0.005))


def test_kernel_seconds_by_name_or_program():
    s = trace.summarize(_events(), _modules(), _spans())
    assert s.seconds(r"^%beam_hop_pallas") == pytest.approx(0.015)
    assert s.seconds(r"^jit_l2_topk$", of="modules") == pytest.approx(0.044)
    assert s.seconds(r"no_such_kernel") is None


def test_nothing_to_read_gives_none():
    assert trace.summarize(_events(), _modules(), []) is None
    assert trace.summarize([], _modules(), _spans()) is None


def test_busy_averages_over_devices():
    evs = [Event("/device:TPU:0", "a", 10 * MS, 50 * MS),
           Event("/device:TPU:1", "a", 10 * MS, 10 * MS)]
    s = trace.summarize(evs, [], [Span("window", 0, 100 * MS)])
    assert s.busy_s == pytest.approx(0.030)
    assert s.top_ops["a"] == pytest.approx(0.030)


def test_ops_get_their_program_and_short_names():
    ops = [Event(DEV, "%fusion.1", 10, 5), Event(DEV, "%fusion.2", 50, 5)]
    mods = [Event(DEV, "jit_f", 8, 10), Event(DEV, "jit_g", 45, 20)]
    got = trace._in_program(ops, mods)
    assert [e.program for e in got] == ["jit_f", "jit_g"]
    assert trace.op_name("%fusion.22 = (f32[1024,10]) fusion(%a)") == \
        "%fusion.22"
    assert trace.program_name("jit_l2_topk(15392294514201234406)") == \
        "jit_l2_topk"


def test_recorded_trace_loads(tmp_path):
    """A trace recorded here (CPU: host planes only) loads; its harness
    spans are found, and with no device plane there is nothing to
    summarize."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x @ x.T, axis=1))
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("flush"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, modules, spans = trace.load(tmp_path)
    assert {s.name for s in spans} >= {"window", "flush"}
    assert all(e.device.startswith("/device:") for e in ops + modules)
    if not ops:
        assert trace.summarize(ops, modules, spans) is None


def test_missing_trace_dir_reads_nothing(tmp_path):
    assert trace.load(tmp_path / "none") == ([], [], [])


def test_recorded_v5e_trace():
    """A 0.25 s traced window of the flat cell recorded on a TPU v5 lite:
    its device ops, the ``jit_l2_topk`` runs and the harness spans."""
    ops, modules, spans = trace.load(Path(__file__).parent / "data")
    s = trace.summarize(ops, modules, spans)
    assert {sp.name for sp in spans} >= {"window", "submit", "flush", "take"}
    assert {e.device for e in ops} == {"/device:TPU:0"}
    assert 0 < s.busy_s <= s.window_s
    assert s.seconds(r"^jit_l2_topk$", of="modules") > 0.8 * s.busy_s
    top = next(iter(s.top_ops))
    assert top.startswith("%fusion") and top.endswith("[jit_l2_topk]")
    assert s.idle_gaps and all(name in ("flush", "submit", "take",
                                        "between spans")
                               for name, _ in s.idle_gaps)
