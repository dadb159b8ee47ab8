"""Process start to the window's start: imports, reaching the chip, the
corpus, building or restoring the index, the query sets and the warm-up
(compiles included where the cache is cold)."""
UNIT, BETTER, SOURCE, LAYER, MOVES = "s", "lower", "host_clock", None, None


def reduce(run):
    return run.setup_s
