"""Share of the traced window in which the device ran no operation."""
UNIT, BETTER, SOURCE, LAYER, MOVES = "%", "lower", "device_trace", \
    "device", "qps"


def reduce(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
