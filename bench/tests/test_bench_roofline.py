"""Roofline arithmetic and the table of peaks."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import roofline  # noqa: E402


def test_v5e_peaks_are_the_published_ones():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12
    assert p["hbm_byte_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_share_takes_the_larger_bound_and_names_it():
    p = {"bf16_flop_per_s": 100.0, "hbm_byte_per_s": 10.0}
    # 50 FLOP -> 0.5 s, 20 B -> 2 s: memory-bound, least time 2 s of 4 s
    assert roofline.share(50, 20, 4.0, p) == (pytest.approx(50.0), "memory")
    # 800 FLOP -> 8 s: compute-bound, 8 s of 10 s
    assert roofline.share(800, 20, 10.0, p) == (pytest.approx(80.0),
                                                "compute")
    assert roofline.share(1, 1, 0.0, p) is None
    assert roofline.share(1, 1, None, p) is None


def test_beam_hop_work_counts_ids_and_rows_only():
    flop, byte = roofline.beam_hop_work(lane_hops=10, gathered=300,
                                        degree=32, dim=600)
    assert byte == 10 * 32 * 4 + 300 * 600 * 4
    assert flop == 2 * 600 * 300


def test_flat_scan_work():
    flop, byte = roofline.flat_scan_work(queries=1000, rows=300_000,
                                         dim=768, batches=1)
    assert flop == 2 * 1000 * 300_000 * 768
    assert byte == 300_000 * 768 * 4
