"""Exact brute force's share of its roofline.

Time: device seconds of the runs of the program whose name matches
``PATTERN`` (``jit_l2_topk``, the jitted scan ``FlatIndex.search`` calls). Work (``bench/roofline.flat_scan_work``): 2*Q*N*D
operations over the window's real queries, and one read of the N x D
float32 table per flush.
"""
from bench import roofline

UNIT, BETTER, SOURCE, LAYER, MOVES = "%", "higher", "device_trace", \
    "index search", "qps"
PATTERN = r"^jit_l2_topk$"


def reduce(run):
    if run.trace is None:
        return None
    seconds = run.trace.seconds(PATTERN, of="modules")
    f = run.window.flushes
    if not seconds or not f:
        return None
    flop, byte = roofline.flat_scan_work(
        sum(x.rows for x in f), run.shape["rows"], run.shape["dim"], len(f))
    out = roofline.share(flop, byte, seconds,
                         roofline.peaks(run.device_kind))
    return out[0] if out else None
