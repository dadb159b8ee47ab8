"""Median host time of one serve-queue flush in the window (the
harness's span around ``maybe_flush`` when it flushed)."""
import numpy as np

UNIT, BETTER, SOURCE, LAYER, MOVES = "ms", "lower", "host_clock", \
    "serve loop", "qps"


def reduce(run):
    f = run.window.flushes
    return float(np.median([x.end - x.start for x in f]) * 1e3) if f else None
