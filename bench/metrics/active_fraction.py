"""Useful share of the lock-step hop loop: hops over hops plus the hops
lanes rode after their own end, weighted by hops over the window."""
UNIT, BETTER, SOURCE, LAYER, MOVES = "ratio", "higher", "program_counter", \
    "traversal", "qps"


def reduce(run):
    f = [x for x in run.window.flushes if x.stats]
    hops = sum(x.stats["hops"] for x in f)
    wasted = sum(x.stats["wasted_hops"] for x in f)
    return hops / (hops + wasted) if hops + wasted else None
