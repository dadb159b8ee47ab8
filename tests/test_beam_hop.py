"""Serving-knob plumbing + traffic accounting for the fused beam hop.

Kernel-level bit-parity lives in tests/test_kernels.py; this module covers
the layers above it: the backend resolvers, the
``hop_backend`` knob's path through SearchParams / IndexParams / the
factory grammar / the sharded wrapper, the per-hop work counters surfaced
by ``TunedGraphIndex.search_stats()``, and the per-hop HBM traffic model
the ISSUE gates on (``repro.analysis.hop_traffic``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hop_traffic import (
    fused_hop_traffic, hop_traffic_report, staged_hop_traffic,
)
from repro.core.beam_search import beam_search, resolve_hop_backend
from repro.core.index_api import SearchParams, build_index


# ------------------------------------------------------------- resolvers
def test_resolve_hop_backend_values():
    assert resolve_hop_backend("staged") == "staged"
    assert resolve_hop_backend("fused") == "fused"
    expected = "fused" if jax.default_backend() == "tpu" else "staged"
    assert resolve_hop_backend(None) == expected
    assert resolve_hop_backend("auto") == expected
    with pytest.raises(ValueError, match="hop backend"):
        resolve_hop_backend("bogus")


# ---------------------------------------------------- SearchParams plumbing
def test_hop_backend_no_retrace(small_nsg, ann_data):
    """``hop_backend`` rides SearchParams as jit-static meta: repeated
    searches with the same value reuse the compiled beam; flipping the
    value is at most one fresh compile (then stable again)."""
    idx = small_nsg
    q = ann_data["queries"][:8]
    sp = SearchParams(ef_search=24, hop_backend="fused")
    idx.search(q, 10, sp)
    misses0 = beam_search._cache_size()
    for _ in range(3):
        idx.search(q, 10, sp)
    assert beam_search._cache_size() == misses0

    sp2 = SearchParams(ef_search=24, hop_backend="staged")
    idx.search(q, 10, sp2)
    flipped = beam_search._cache_size()
    assert flipped <= misses0 + 1
    idx.search(q, 10, sp2)
    assert beam_search._cache_size() == flipped


# ------------------------------------------------------- stats surfacing
def test_search_stats_surfacing(small_nsg, ann_data):
    idx = small_nsg
    q = ann_data["queries"][:12]
    r = idx.graph.neighbors.shape[1]
    for hop in ("staged", "fused"):
        d, i = idx.search(q, 10, ef=24, hop_backend=hop)
        st = idx.search_stats()
        assert set(st) >= {"hops", "gathered", "dup_gathered",
                           "wasted_hops", "active_fraction",
                           "mean_hops", "p99_hops", "fetch_share"}
        assert st["hops"] > 0
        # every hop expands at most one R-row; dups are a subset of gathers
        assert 0 < st["gathered"] <= st["hops"] * r
        assert 0 <= st["dup_gathered"] <= st["gathered"]
        # the share of candidate slots the fused hop fetches a row for
        assert st["fetch_share"] == st["gathered"] / (
            r * (st["hops"] + st["wasted_hops"]))
        # small_nsg's rows are -1-padded past their out-degree
        assert 0 < st["fetch_share"] < 1


def test_search_stats_work_parity_quantized(small_nsg, ann_data):
    """Fused and staged count identical work through the pipeline's
    quantized path (same arithmetic on CPU -> same trajectory): the
    counters back work-parity assertions, not just plausibility checks."""
    idx = small_nsg
    q = ann_data["queries"][:12]
    idx.search(q, 10, ef=24, dist_backend="pq", hop_backend="staged")
    staged = idx.search_stats()
    idx.search(q, 10, ef=24, dist_backend="pq", hop_backend="fused")
    fused = idx.search_stats()
    assert staged == fused


# --------------------------------------------------------- traffic model
def test_hop_traffic_gate_at_pinned_config():
    """The ISSUE's acceptance gate: >= 2x lower per-hop spilled HBM
    traffic at the pinned bench config (ef=64, R=24, dim=96), f32 and pq."""
    for backend, pq_m in (("f32", 0), ("pq", 48)):
        rep = hop_traffic_report(64, 24, 96, backend, pq_m=pq_m)
        assert rep["spill_reduction_vs_staged"] >= 2.0
        assert rep["total_reduction_vs_staged"] > 1.0
        assert (rep["fused_total_bytes_per_hop"]
                < rep["staged_total_bytes_per_hop"])


def test_hop_traffic_model_structure():
    st = staged_hop_traffic(48, 12, 32)
    fu = fused_hop_traffic(48, 12, 32)
    # compulsory streams are identical by construction; only spill differs
    assert st.compulsory == fu.compulsory
    assert st.spilled / fu.spilled >= 2.0
    assert st.total == st.compulsory + st.spilled
    # pq rows are M bytes, not D*4: compulsory must shrink
    assert (staged_hop_traffic(48, 12, 32, "pq", pq_m=16).compulsory
            != st.compulsory)


# --------------------------------------------- factory / sharded plumbing
def test_factory_hop_token_and_override(ann_data):
    data = ann_data["data"][:600]
    idx = build_index("NSG12,EP8,HopFused", data, key=jax.random.PRNGKey(0))
    assert idx.params.hop_backend == "fused"
    d, i = idx.search(ann_data["queries"][:8], 10)
    assert i.shape == (8, 10)
    assert idx.search_stats()["hops"] > 0

    idx2 = build_index("NSG12,EP8", data, key=jax.random.PRNGKey(0),
                       hop_backend="staged")
    assert idx2.params.hop_backend == "staged"

    with pytest.raises(ValueError):
        build_index("NSG12,HopTurbo", data, key=jax.random.PRNGKey(0))


def test_sharded_factory_threads_hop_backend(ann_data):
    from repro.core.distributed import ShardedFactoryIndex
    idx = ShardedFactoryIndex("NSG8,EP2", n_shards=2,
                              hop_backend="fused").fit(
        ann_data["data"][:400], key=jax.random.PRNGKey(0))
    assert all(s.params.hop_backend == "fused" for s in idx.subs)
    d, i = idx.search(ann_data["queries"][:4], 5)
    assert i.shape == (4, 5)
    assert np.asarray(i).max() < 400


def test_default_space_has_hop_backend():
    from repro.core.tuning.objective import default_space
    space = default_space(32, 2000)
    assert "hop_backend" in space.names()
