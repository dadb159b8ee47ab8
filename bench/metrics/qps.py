"""Queries answered over the whole window, per second of it."""
UNIT, BETTER, SOURCE, LAYER, MOVES = "queries/s", "higher", "host_clock", \
    None, None


def reduce(run):
    w = run.window
    return w.answered / w.seconds if w.seconds > 0 else None
