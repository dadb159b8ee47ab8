"""Roofline arithmetic: the chip's peaks and the least time a piece of
work could take on it.

Peaks come from ``peaks.json``, keyed by the ``device_kind`` JAX reports;
a kind that is not listed is an error, never a default. The operations
and bytes a kernel needs are computed here from its shapes, not read from
the program or its compiled code.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Optional, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    kinds = json.loads(PEAKS.read_text())["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(kinds)}")
    return kinds[device_kind]


def share(flop: float, byte: float, seconds: Optional[float],
          peak: dict) -> Optional[Tuple[float, str]]:
    """(percent of the roofline, the bound: "compute" or "memory").

    The least time is the larger of operations over peak bf16 FLOP/s and
    bytes over peak HBM bytes/s. None where no time was measured.
    """
    if not seconds or seconds <= 0:
        return None
    t_flop = flop / peak["bf16_flop_per_s"]
    t_byte = byte / peak["hbm_byte_per_s"]
    bound = "compute" if t_flop >= t_byte else "memory"
    return 100.0 * max(t_flop, t_byte) / seconds, bound


def beam_hop_work(lane_hops: int, gathered: int, degree: int,
                  dim: int) -> Tuple[float, float]:
    """(FLOP, bytes) the f32 beam hop needs: per live lane-hop one row of
    ``degree`` int32 neighbour ids; per candidate scored one float32 row
    of ``dim`` and 2*dim operations. No tile or padding bytes."""
    byte = lane_hops * degree * 4 + gathered * dim * 4
    return 2.0 * dim * gathered, float(byte)


def flat_scan_work(queries: int, rows: int, dim: int,
                   batches: int) -> Tuple[float, float]:
    """(FLOP, bytes) of exact brute force: 2*Q*N*D operations over all
    real queries, and one read of the N x D float32 table per batch."""
    return 2.0 * queries * rows * dim, float(batches) * rows * dim * 4
