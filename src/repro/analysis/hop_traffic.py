"""Per-hop HBM traffic model for the beam-search hop backends.

The fused beam-hop kernel (``kernels/beam_hop``) exists to eliminate the
*spilled* intermediate traffic of the staged hop — the (Q, R) candidate
blocks and sort permutations that the staged ops materialize in HBM
between gather, distance, and merge. This module prices one hop of one
active query for both backends, split into:

  * **compulsory** bytes — traffic any implementation must move: the R
    candidate rows streamed from the database table (f32 vectors or uint8
    codes), the graph adjacency row, and the per-query score operand (the
    query vector for f32, the ADC LUT for pq/int8). Identical for both
    backends by construction (the work-parity counters in
    ``TunedGraphIndex.search_stats()`` assert the *row counts* match).

  * **spilled** bytes — hot-state round trips. The staged hop writes and
    re-reads the candidate ids (gather -> distance -> merge, 3 touches),
    the candidate distances (distance -> merge), the (ef + R) concat block
    and the stable-argsort permutation inside the merge, plus the pool
    itself. The fused kernel keeps all of that in VMEM/registers: only the
    (ef) pool state (read + write), the selected id, and the stats pair
    cross the HBM boundary.

Byte prices are the repro pipeline's actual dtypes: pool slot = 9 bytes
(i32 id + f32 dist + bool visited), ids i32, dists f32. The model is a
deliberate lower bound for staged (XLA may fuse some adjacent pairs, may
also spill more); the ISSUE gate runs on the **spilled** ratio, where the
advantage is architectural rather than compiler-dependent — the total
ratio is reported alongside for context.
"""
from __future__ import annotations

from dataclasses import dataclass

# Pool slot: id (i32) + distance (f32) + visited flag (bool) per lane.
POOL_SLOT_BYTES = 9
_I32 = 4
_F32 = 4


@dataclass(frozen=True)
class HopTraffic:
    """Bytes moved through HBM for ONE hop of ONE active query."""
    compulsory: int
    spilled: int

    @property
    def total(self) -> int:
        return self.compulsory + self.spilled


def _compulsory(r: int, dim: int, dist_backend: str, pq_m: int,
                pq_c: int) -> int:
    if dist_backend == "f32":
        rows = r * dim * _F32            # R database vectors
        operand = dim * _F32             # the query vector
    else:
        m = pq_m if pq_m else max(1, dim // 2)
        rows = r * m                     # R uint8 code rows
        operand = m * pq_c * _F32        # the per-query ADC LUT
    graph_row = r * _I32                 # the adjacency row of the frontier
    return rows + graph_row + operand


def staged_hop_traffic(ef: int, r: int, dim: int,
                       dist_backend: str = "f32", pq_m: int = 0,
                       pq_c: int = 256) -> HopTraffic:
    """Staged ops: gather -> distance kernel -> argsort merge, HBM between.

    Spilled inventory (writes + the re-reads they imply):
      * pool state read + write                      2 * ef * 9
      * merge concat block written then re-read      2 * (ef + R) * 9
      * stable-argsort permutation written + read    2 * (ef + R) * 4
      * candidate ids: gather out, distance in,
        merge in                                     3 * R * 4
      * candidate distances: distance out, merge in  2 * R * 4
      * selected frontier id + active flag           8
    """
    spilled = (2 * ef * POOL_SLOT_BYTES
               + 2 * (ef + r) * POOL_SLOT_BYTES
               + 2 * (ef + r) * _I32
               + 3 * r * _I32
               + 2 * r * _F32
               + 8)
    return HopTraffic(_compulsory(r, dim, dist_backend, pq_m, pq_c), spilled)


def fused_hop_traffic(ef: int, r: int, dim: int,
                      dist_backend: str = "f32", pq_m: int = 0,
                      pq_c: int = 256) -> HopTraffic:
    """Fused kernel: the (Q, R) block lives and dies in VMEM.

    Spilled inventory: pool read + write (2 * ef * 9), the scalar-prefetched
    selected id (+ flag, 8), and the (2,) i32 stats write (8).
    """
    spilled = 2 * ef * POOL_SLOT_BYTES + 8 + 8
    return HopTraffic(_compulsory(r, dim, dist_backend, pq_m, pq_c), spilled)


def hop_traffic_report(ef: int, r: int, dim: int,
                       dist_backend: str = "f32", pq_m: int = 0,
                       pq_c: int = 256) -> dict:
    """Both backends priced at one hop config, with the gate ratios.

    ``spill_reduction`` (staged spilled / fused spilled) is the
    architectural win the ISSUE gates at >= 2x; ``total_reduction``
    includes the compulsory floor both backends share.
    """
    st = staged_hop_traffic(ef, r, dim, dist_backend, pq_m, pq_c)
    fu = fused_hop_traffic(ef, r, dim, dist_backend, pq_m, pq_c)
    return {
        "ef": ef, "r": r, "dim": dim, "dist_backend": dist_backend,
        "compulsory_bytes_per_hop": st.compulsory,
        "staged_spilled_bytes_per_hop": st.spilled,
        "fused_spilled_bytes_per_hop": fu.spilled,
        "staged_total_bytes_per_hop": st.total,
        "fused_total_bytes_per_hop": fu.total,
        "spill_reduction_vs_staged": round(st.spilled / fu.spilled, 3),
        "total_reduction_vs_staged": round(st.total / fu.total, 3),
    }


def traversal_savings_report(stats: dict, ef: int, r: int, dim: int,
                             dist_backend: str = "f32", pq_m: int = 0,
                             pq_c: int = 256, hop_backend: str = "staged",
                             baseline_stats: dict = None) -> dict:
    """Price a traversal's straggler waste in modeled HBM bytes.

    ``stats`` is a ``TunedGraphIndex.search_stats()`` dict. ``hops`` hops
    did real work; ``wasted_hops`` are lock-stepped no-op hops the batch
    executed for lanes that had already converged — every one of them moves
    the full per-hop byte bill for zero pool change. Adaptive termination
    (patience/eps) shrinks the useful count by stopping lanes before
    full-pool convergence, and the wasted count when the batch's last lane
    stops sooner. Pass the ``patience=None`` run's stats as
    ``baseline_stats`` to get the cross-run reduction ratios the ISSUE
    gate (>= 1.3x fewer total hops) is checked against.
    """
    traffic = (fused_hop_traffic if hop_backend == "fused"
               else staged_hop_traffic)(ef, r, dim, dist_backend, pq_m, pq_c)
    useful = int(stats["hops"])
    wasted = int(stats["wasted_hops"])
    launched = useful + wasted
    report = {
        "ef": ef, "r": r, "dim": dim, "dist_backend": dist_backend,
        "hop_backend": hop_backend,
        "bytes_per_hop": traffic.total,
        "useful_hops": useful,
        "wasted_hops": wasted,
        "launched_hops": launched,
        "active_fraction": round(useful / max(launched, 1), 4),
        "useful_bytes": useful * traffic.total,
        "wasted_bytes": wasted * traffic.total,
    }
    if baseline_stats is not None:
        base_useful = int(baseline_stats["hops"])
        base_launched = base_useful + int(baseline_stats["wasted_hops"])
        report["baseline_useful_hops"] = base_useful
        report["baseline_launched_hops"] = base_launched
        report["hop_reduction_vs_baseline"] = round(
            base_useful / max(useful, 1), 3)
        report["launched_reduction_vs_baseline"] = round(
            base_launched / max(launched, 1), 3)
        report["bytes_saved_vs_baseline"] = (
            (base_launched - launched) * traffic.total)
    return report
