"""The f32 beam-hop kernel's share of its roofline.

Time: device seconds of the trace's ops whose name matches ``PATTERN``
(on a v5e the kernel's op is ``%beam_hop_pallas.<n>``, inside the
``jit_beam_search`` program).
Work (``bench/roofline.beam_hop_work``): per live lane-hop of the window
one row of R neighbour ids, per candidate scored one d' float32 row and
2*d' operations, from the program's ``hops`` and ``gathered`` counters.
"""
from bench import roofline

UNIT, BETTER, SOURCE, LAYER, MOVES = "%", "higher", "device_trace", \
    "kernels", "qps"
PATTERN = r"^%beam_hop_pallas"


def reduce(run):
    if run.trace is None:
        return None
    seconds = run.trace.seconds(PATTERN, of="ops")
    f = [x for x in run.window.flushes if x.stats]
    if not seconds or not f:
        return None
    flop, byte = roofline.beam_hop_work(
        sum(x.stats["hops"] for x in f), sum(x.stats["gathered"] for x in f),
        run.shape["degree"], run.shape["dim"])
    out = roofline.share(flop, byte, seconds,
                         roofline.peaks(run.device_kind))
    return out[0] if out else None
