"""Reduce a profiler trace (``.xplane.pb``) to device time and idle gaps.

``load`` reads the newest trace under a directory with
``jax.profiler.ProfileData`` into plain tuples; ``summarize`` does the
arithmetic, so tests can feed it events of their own.

On a TPU each ``/device:`` plane has an ``XLA Ops`` line (one event per
HLO op, named by the op's HLO text, whose own name is kept: the text
before " = ") and an ``XLA Modules`` line (one event per program run,
named ``jit_<function>(<hash>)``, whose function name is kept). Busy time
is the union of the op intervals inside the harness's ``window`` span,
averaged over the devices that ran anything. Idle gaps are the stretches
of that window in which the device ran nothing, named by the innermost
harness span open on the host at the gap's midpoint.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "window"
HARNESS_SPANS = ("window", "submit", "flush", "take")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CONTAINER_OPS = re.compile(r"^%?while")   # their time is their body's ops


@dataclass(frozen=True)
class Event:
    device: str               # plane name, e.g. "/device:TPU:0"
    name: str                 # op name ("%fusion.3") or program ("jit_f")
    start_ns: float
    dur_ns: float
    program: str = ""         # for an op: the program it ran in

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Summary:
    window_s: float
    busy_s: float                          # averaged over active devices
    ops: List[Event]                       # device ops in the window
    modules: List[Event] = field(default_factory=list)   # program runs
    top_ops: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def seconds(self, pattern: str, of: str = "ops") -> Optional[float]:
        """Device seconds of the ops (or program runs, ``of="modules"``)
        whose name matches; None where nothing matches."""
        rx = re.compile(pattern)
        events = self.ops if of == "ops" else self.modules
        hit = [e.dur_ns for e in events if rx.search(e.name)]
        return sum(hit) / 1e9 / max(1, _n_devices(events)) if hit else None


def _n_devices(events: Sequence[Event]) -> int:
    return len({e.device for e in events})


def op_name(hlo_text: str) -> str:
    return hlo_text.split(" = ", 1)[0]


def program_name(module_text: str) -> str:
    return module_text.split("(", 1)[0]


def _in_program(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Ops with the program whose run encloses their start."""
    mods = sorted(modules, key=lambda m: (m.device, m.start_ns))
    by_dev: Dict[str, List[Event]] = {}
    for m in mods:
        by_dev.setdefault(m.device, []).append(m)
    starts = {d: [m.start_ns for m in ms] for d, ms in by_dev.items()}
    out = []
    for e in ops:
        ms = by_dev.get(e.device, [])
        i = bisect.bisect_right(starts.get(e.device, []), e.start_ns) - 1
        prog = ms[i].name if i >= 0 and e.start_ns <= ms[i].end_ns else ""
        out.append(Event(e.device, e.name, e.start_ns, e.dur_ns, prog))
    return out


def load(trace_dir: Path) -> Tuple[List[Event], List[Event], List[Span]]:
    """Device ops, program runs and harness spans of the newest trace
    under a directory."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return [], [], []
    pd = ProfileData.from_file(str(files[-1]))
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Event(plane.name, op_name(ev.name), ev.start_ns,
                                  ev.duration_ns) for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules += [Event(plane.name, program_name(ev.name),
                                      ev.start_ns, ev.duration_ns)
                                for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HARNESS_SPANS:
                        spans.append(Span(ev.name, ev.start_ns, ev.end_ns))
    return _in_program(ops, modules), modules, spans


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _span_at(spans: List[Span], t: float) -> str:
    best = None
    for sp in spans:
        if sp.start_ns <= t <= sp.end_ns and sp.name != WINDOW_SPAN:
            if best is None or sp.end_ns - sp.start_ns < \
                    best.end_ns - best.start_ns:
                best = sp
    return best.name if best else "between spans"


def summarize(ops: List[Event], modules: List[Event], spans: List[Span],
              top: int = 10) -> Optional[Summary]:
    """None when the trace holds no window or no device op in it."""
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if not windows:
        return None
    w0, w1 = windows[-1].start_ns, windows[-1].end_ns
    inside = [e for e in ops if e.end_ns > w0 and e.start_ns < w1]
    if not inside:
        return None
    busy, gaps = 0.0, []
    for dev in sorted({e.device for e in inside}):
        merged = _union([(max(e.start_ns, w0), min(e.end_ns, w1))
                         for e in inside if e.device == dev])
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) / 2))
    n_dev = _n_devices(inside)
    totals: Dict[str, float] = {}
    for e in inside:
        if CONTAINER_OPS.search(e.name):
            continue
        key = f"{e.name} [{e.program}]" if e.program else e.name
        totals[key] = totals.get(key, 0.0) + e.dur_ns / 1e9 / n_dev
    host = [s for s in spans if s.end_ns > w0 and s.start_ns < w1]
    gaps.sort(reverse=True)
    named = [(_span_at(host, mid), dur / 1e9) for dur, mid in gaps[:top]]
    top_ops = dict(sorted(totals.items(), key=lambda kv: -kv[1])[:top])
    mods = [m for m in modules if m.end_ns > w0 and m.start_ns < w1]
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy / 1e9 / n_dev,
                   ops=inside, modules=mods, top_ops=top_ops,
                   idle_gaps=named)
