"""Share of the traced window in which the device ran no op while the
host was inside the program's ``queue.h2d`` or ``queue.d2h`` span: the
request put on the device, and the wait for the answers and their copy
back (``bench/spans.py``). Part of ``device_idle_share``."""
from bench import spans

UNIT, BETTER, SOURCE, LAYER, MOVES = "%", "lower", "device_trace", \
    "serve loop", "qps"


def reduce(run):
    att = spans.for_run(run)
    return att.share(spans.TRANSFER) if att else None
