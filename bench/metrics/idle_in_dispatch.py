"""Share of the traced window in which the device ran no op while the
host was inside the program's ``search.call`` span: the bucket's pad and
slice, the index's search stages and their dispatch, and any compile under
them (``bench/spans.py``). Part of ``device_idle_share``."""
from bench import spans

UNIT, BETTER, SOURCE, LAYER, MOVES = "%", "lower", "device_trace", \
    "serve loop", "qps"


def reduce(run):
    att = spans.for_run(run)
    return att.share(spans.DISPATCH) if att else None
