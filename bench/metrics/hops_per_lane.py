"""Beam-search hops per lane dispatched: the program's ``hops`` counter
summed over the window's flushes, over the rows those flushes sent to
the search (padding rows are lanes too)."""
UNIT, BETTER, SOURCE, LAYER, MOVES = "hops", "lower", "program_counter", \
    "traversal", "qps"


def reduce(run):
    f = [x for x in run.window.flushes if x.stats]
    lanes = sum(x.padded for x in f)
    if not lanes:
        return None
    return sum(x.stats["hops"] for x in f) / lanes
