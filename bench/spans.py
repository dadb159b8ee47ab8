"""Idle device time put down to the program's own host spans.

The program wraps each stage of its serve path in a named profiler span
(``repro.serve.spans.SPANS``: ``queue.flush``, ``queue.h2d``,
``search.call``, ``queue.d2h`` ...); JAX wraps its backend compiles in
spans of their own (``COMPILE_SPANS``). ``load`` reads those host events,
and the harness's, from the newest trace under a directory. ``attribute``
takes the same idle intervals ``bench/trace.summarize`` takes (the stretches
of the harness's ``window`` in which a device ran no op) and splits them by
the spans open over them. A program without spans (an older tree) gives no
attribution, and the metrics that read one leave themselves out.

    python3 bench/spans.py <trace dir>

prints the idle seconds under each span and the longest idle gaps, each
named by the innermost span open at its midpoint, as one JSON object.
"""
from __future__ import annotations

import functools
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from bench.trace import Event, Span  # noqa: E402

COMPILE_SPANS = ("backend_compile", "backend_compile_and_load")
DISPATCH = ("search.call",)
TRANSFER = ("queue.h2d", "queue.d2h")
Interval = Tuple[float, float]


def program_spans() -> Tuple[str, ...]:
    """The span names the program emits; none where it has no spans."""
    try:
        from repro.serve.spans import SPANS
    except ImportError:
        return ()
    return tuple(SPANS)


def newest(trace_dir: Path) -> Optional[Path]:
    """The newest ``.xplane.pb`` under a directory, if any."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def load(trace_dir: Path, names: Iterable[str]) -> List[Span]:
    """Host events named in ``names`` from the newest trace under a
    directory, as spans."""
    path = newest(trace_dir)
    if path is None:
        return []
    return list(_load(str(path), path.stat().st_mtime_ns, tuple(names)))


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int, names: Tuple[str, ...]
          ) -> Tuple[Span, ...]:
    """One read of a trace file, kept for the next reducer of the run."""
    from jax.profiler import ProfileData
    wanted = frozenset(names)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [Span(ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events if ev.name in wanted]
    return tuple(out)


def all_names() -> Tuple[str, ...]:
    return trace.HARNESS_SPANS + program_spans() + COMPILE_SPANS


@dataclass
class Attribution:
    window: Interval                    # the harness's window, ns
    idle: Dict[str, List[Interval]]     # device -> its idle intervals, ns
    spans: List[Span]                   # host spans overlapping the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def idle_s(self, names: Optional[Iterable[str]] = None) -> float:
        """Idle seconds, averaged over devices, in which a span of
        ``names`` was open (all idle seconds where ``names`` is None)."""
        total = 0.0
        if names is None:
            for ivs in self.idle.values():
                total += sum(e - s for s, e in ivs)
        else:
            names = set(names)
            cover = trace._union([(sp.start_ns, sp.end_ns)
                                  for sp in self.spans if sp.name in names])
            for ivs in self.idle.values():
                total += _overlap(ivs, cover)
        return total / 1e9 / max(1, len(self.idle))

    def share(self, names: Iterable[str]) -> float:
        """``idle_s(names)`` as a percentage of the window."""
        return 100.0 * self.idle_s(names) / self.window_s

    def by_span(self) -> Dict[str, float]:
        """Idle seconds under each span name (nested spans each count)."""
        names = sorted({sp.name for sp in self.spans}
                       - {trace.WINDOW_SPAN})
        return {n: self.idle_s((n,)) for n in names}

    def gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The ``top`` longest idle gaps in seconds, longest first, each
        named by the innermost span open at its midpoint."""
        gaps = sorted(((e - s, (s + e) / 2) for ivs in self.idle.values()
                       for s, e in ivs), reverse=True)
        return [(trace._span_at(self.spans, mid), dur / 1e9)
                for dur, mid in gaps[:top]]


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def attribute(ops: List[Event], spans: List[Span]) -> Optional[Attribution]:
    """Idle intervals of the last ``window`` span, the arithmetic of
    ``trace.summarize``; None where there is no window or no op in it."""
    windows = [s for s in spans if s.name == trace.WINDOW_SPAN]
    if not windows:
        return None
    w0, w1 = windows[-1].start_ns, windows[-1].end_ns
    inside = [e for e in ops if e.end_ns > w0 and e.start_ns < w1]
    if not inside:
        return None
    idle = {}
    for dev in sorted({e.device for e in inside}):
        merged = trace._union([(max(e.start_ns, w0), min(e.end_ns, w1))
                               for e in inside if e.device == dev])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle[dev] = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                     if e > s]
    host = [s for s in spans if s.end_ns > w0 and s.start_ns < w1]
    return Attribution((w0, w1), idle, host)


def trace_dir() -> Path:
    """Where ``bench/run.py`` profiles a traced window: ``CACHE / "trace"``
    of the harness module that is running (``__main__`` when it runs as
    a script), so a cache the tests move is followed."""
    for name in ("bench.run", "__main__"):
        cache = getattr(sys.modules.get(name), "CACHE", None)
        if cache is not None:
            return Path(cache) / "trace"
    return ROOT / "bench" / "cache" / "trace"


def for_run(run) -> Optional[Attribution]:
    """The attribution of a traced run's window.

    None where the run has no trace summary, where the newest trace's
    window is not the summary's, or where no span of the program falls
    in the window. Otherwise the summary's ``idle_gaps``, which the
    harness prints as the result line's ``breakdown`` after the metrics,
    are named again by the program's spans as well as the harness's.
    """
    summary = run.trace
    if summary is None or not program_spans():
        return None
    att = attribute(summary.ops, load(trace_dir(), all_names()))
    if (att is None or abs(att.window_s - summary.window_s) > 1e-6
            or not set(program_spans()) & {sp.name for sp in att.spans}):
        return None
    summary.idle_gaps = att.gaps(len(summary.idle_gaps))
    return att


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        sys.exit("usage: python3 bench/spans.py <trace dir>")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    ops, _, _ = trace.load(Path(argv[0]))
    att = attribute(ops, load(Path(argv[0]), all_names()))
    if att is None:
        sys.exit("bench/spans.py: no window with device ops in the trace")
    print(json.dumps({
        "window_s": att.window_s,
        "idle_s": att.idle_s(),
        "idle_by_span": att.by_span(),
        "idle_in_dispatch": att.share(DISPATCH),
        "idle_in_transfer": att.share(TRANSFER),
        "idle_gaps": att.gaps(20)}))


if __name__ == "__main__":
    main()
