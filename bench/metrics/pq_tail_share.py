"""Share of the traced window's device busy time spent in the quantized
search's two programs around the traversal: the ADC table build
(``jit_pq_lut``, under the program's ``search.lut`` span) and the exact
float32 rerank of the beam's survivors (``jit__exact_rerank``, under
``search.rerank``), by the device seconds of their runs.

Reading it also names the breakdown's idle gaps by the program's spans
(``bench/spans.for_run``), as the ``idle_in_*`` reducers do in the cells
that report them.
"""
from bench import spans

UNIT, BETTER, SOURCE, LAYER, MOVES = "%", "lower", "device_trace", \
    "index search", "qps"
PATTERN = r"^jit_(pq_lut|_exact_rerank)$"


def reduce(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    spans.for_run(run)
    seconds = t.seconds(PATTERN, of="modules")
    return None if seconds is None else 100.0 * seconds / t.busy_s
