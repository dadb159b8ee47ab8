"""The PQ beam-hop kernel's share of its roofline.

Time: device seconds of the trace's ops whose name matches ``PATTERN``
(on a v5e the kernel's op is ``%beam_hop_pallas.<n>``, inside the
``jit_beam_search`` program).
Work (``pq_hop_work``): what any implementation of the hop must touch,
whatever the kernel does. Per live lane-hop one row of R int32 neighbour
ids (the program's ``hops`` counter); per candidate scored its M code
bytes and M adds (``gathered``); per lane searched one M x C float32 ADC
table (the flushes' padded rows), read once per search, not once per hop,
so a hop that keeps the table resident across hops stays under 100%.
"""
from bench import roofline

UNIT, BETTER, SOURCE, LAYER, MOVES = "%", "higher", "device_trace", \
    "kernels", "qps"
PATTERN = r"^%beam_hop_pallas"
CENTROIDS = 256            # PQ<m>x8: one byte per code


def pq_hop_work(lane_hops: int, gathered: int, lanes: int, degree: int,
                m: int, centroids: int = CENTROIDS):
    """(operations, bytes) of the PQ hops of a window: ``lane_hops`` rows
    of ``degree`` int32 ids, ``gathered`` candidates of ``m`` code bytes
    and ``m`` adds each, and one ``m`` x ``centroids`` float32 table per
    lane searched. No tile or padding bytes."""
    byte = lane_hops * degree * 4 + gathered * m + lanes * m * centroids * 4
    return float(m * gathered), float(byte)


def reduce(run):
    if run.trace is None:
        return None
    m = run.config.get("index", {}).get("ann_config", {}).get("pq_m")
    seconds = run.trace.seconds(PATTERN, of="ops")
    f = [x for x in run.window.flushes if x.stats]
    if not m or not seconds or not f:
        return None
    flop, byte = pq_hop_work(
        sum(x.stats["hops"] for x in f), sum(x.stats["gathered"] for x in f),
        sum(x.padded for x in f), run.shape["degree"], m)
    out = roofline.share(flop, byte, seconds,
                         roofline.peaks(run.device_kind))
    return out[0] if out else None
