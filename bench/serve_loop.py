"""The measured window: traffic through the program's serve queue.

Requests go through ``MicroBatchQueue`` (``submit`` / ``maybe_flush`` /
``take``) over the bucketed search step, as ``launch/serve.py`` serves
them. A closed loop sends the next request when the previous answer is
back; an open loop sends each request when it is due. The window closes at
the end of the first flush that ends after ``seconds``, so it holds whole
flushes only. In an open loop, requests still pending then are flushed
after the close; they count as attempted and in the latency tail, but not
in the window's answered rows.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np


@dataclass
class Flush:
    start: float              # host clock, seconds from the window start
    end: float
    rows: int                 # real query rows answered
    padded: int               # rows dispatched to the search, padding too
    stats: Optional[dict] = None   # the program's search counters


@dataclass
class Window:
    seconds: float = 0.0      # start to the end of the last flush
    flushes: List[Flush] = field(default_factory=list)
    done: List[tuple] = field(default_factory=list)   # (request, result)
    latency_s: List[float] = field(default_factory=list)  # per request
    late_s: List[float] = field(default_factory=list)  # submit - due
    attempted: int = 0        # query rows submitted
    answered: int = 0         # query rows answered inside the window
    compiles: int = 0         # compilations inside the window


def no_span(name: str):
    return contextlib.nullcontext()


def run(queue, schedule, sets: List[np.ndarray], seconds: float, *,
        span: Callable = no_span, read_stats: Optional[Callable] = None,
        compiles: Callable[[], int] = lambda: 0,
        clock: Callable[[], float] = time.perf_counter) -> Window:
    """Drive ``queue`` with ``schedule`` for ``seconds``; see module doc."""
    win = Window()
    search = queue.search
    outstanding = {}          # ticket -> (request, submitted at)
    c0 = compiles()
    t0 = clock()
    i = 0
    closed_at = None

    def collect(now: float) -> None:
        for ticket in [t for t in outstanding if t in queue.results]:
            req, submitted = outstanding.pop(ticket)
            result = queue.take(ticket)
            win.done.append((req, result))
            due = submitted if schedule.closed else t0 + req.due_s
            win.latency_s.append(now - due)
            if closed_at is None and result:
                win.answered += req.rows

    def submit(req) -> None:
        with span("submit"):
            q = sets[req.set_index][req.row0:req.row0 + req.rows]
            now = clock()
            if not schedule.closed:
                win.late_s.append(now - (t0 + req.due_s))
            outstanding[queue.submit(q)] = (req, now)
            win.attempted += req.rows

    seen = queue.flushes
    while True:
        now = clock()
        if schedule.closed:
            if not outstanding:
                submit(schedule[i])
                i += 1
        else:
            while schedule[i].due_s <= now - t0:
                submit(schedule[i])
                i += 1
        if queue.flushes != seen:        # a submit flushed a full bucket
            seen = queue.flushes
            with span("take"):
                collect(clock())
        rows = sum(r.rows for r, _ in outstanding.values())
        n_disp = len(search.dispatched)
        start = clock()
        with span("flush"):
            flushed = queue.maybe_flush()
        end = clock()
        if flushed:
            seen = queue.flushes
            stats = read_stats() if read_stats else None
            win.flushes.append(Flush(start - t0, end - t0, rows,
                                     int(sum(search.dispatched[n_disp:])),
                                     stats))
            with span("take"):
                collect(end)
            if end - t0 >= seconds:
                closed_at = end
                break
        elif schedule.closed:
            time.sleep(queue.window_s / 4)
        else:
            wait = t0 + schedule[i].due_s - clock()
            time.sleep(min(max(wait, 0.0), queue.window_s / 4 or 1e-4))
    win.seconds = closed_at - t0
    win.compiles = compiles() - c0
    if outstanding:                       # open loop: due before the close
        queue.flush()
        collect(clock())
    return win
