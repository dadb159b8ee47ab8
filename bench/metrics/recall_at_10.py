"""recall@10 of the window's answers against the plain reference, over
the seeded sample the check compares."""
UNIT, BETTER, SOURCE, LAYER, MOVES = "ratio", "higher", "host_clock", \
    None, None


def reduce(run):
    if not run.readings.get("sampled"):
        return None
    return run.readings["recall_at_10"]
