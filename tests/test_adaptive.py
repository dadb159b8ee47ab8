"""Adaptive early termination.

Covers the straggler-control layer end to end: ``patience=None`` bit-parity
with the exact-convergence loop (its safety contract), recall monotonicity
in ``patience``, knob plumbing through SearchParams / the factory grammar /
the sharded wrapper / the tuning space, the parse-time PQ ``m | dim``
validation, the serve-queue latency stats, and the hop-traffic savings
pricing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.beam_search import beam_search
from repro.core.index_api import SearchParams, build_index


def _beam_case(ann_data, small_nsg, dist_backend="f32"):
    """(queries, db, neighbors, entries, extra-kwargs) for direct calls."""
    q = ann_data["queries"][:24]
    db = small_nsg.base
    nbrs = small_nsg.graph.neighbors
    entries = jnp.full((q.shape[0],), int(small_nsg.graph.medoid), jnp.int32)
    kw = {}
    if dist_backend != "f32":
        from repro.core.quant.codec import make_codec
        codec = make_codec(dist_backend, db.shape[1], pq_m=8)
        codec.fit(db, key=jax.random.PRNGKey(3))
        kw = dict(dist_backend=dist_backend, codes=codec.encode(db),
                  lut=codec.lut(q))
    return q, db, nbrs, entries, kw


# ------------------------------------------------- patience=None bit-parity
@pytest.mark.parametrize("dist_backend", ["f32", "pq", "int8"])
@pytest.mark.parametrize("hop_backend", ["staged", "fused"])
def test_patience_none_bit_parity(ann_data, small_nsg, dist_backend,
                                  hop_backend):
    """``patience=None`` must reproduce the exact-convergence semantics
    bit-for-bit, and a patience that can never fire (>= max_iters) must be
    indistinguishable from it — ids, dists AND stats."""
    q, db, nbrs, entries, kw = _beam_case(ann_data, small_nsg, dist_backend)
    base = dict(ef=24, k=10, hop_backend=hop_backend, with_stats=True, **kw)
    d0, i0, s0 = beam_search(q, db, nbrs, entries, patience=None, **base)
    d1, i1, s1 = beam_search(q, db, nbrs, entries, patience=4 * 24, **base)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    for a, b in zip(s0, s1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_patience_validation(ann_data, small_nsg):
    q, db, nbrs, entries, _ = _beam_case(ann_data, small_nsg)
    with pytest.raises(ValueError, match="patience"):
        beam_search(q, db, nbrs, entries, ef=16, k=5, patience=0)
    with pytest.raises(ValueError, match="eps"):
        beam_search(q, db, nbrs, entries, ef=16, k=5, eps=-0.5)


def test_adaptive_reduces_hops(ann_data, small_nsg):
    """A small patience must terminate strictly earlier than full-pool
    convergence on real data, and the per-lane early exit rides into the
    wasted-hop accounting."""
    q, db, nbrs, entries, _ = _beam_case(ann_data, small_nsg)
    base = dict(ef=48, k=10, with_stats=True)
    _, _, s_full = beam_search(q, db, nbrs, entries, **base)
    _, _, s_adapt = beam_search(q, db, nbrs, entries, patience=4, **base)
    assert int(jnp.sum(s_adapt.hops)) < int(jnp.sum(s_full.hops))


# --------------------------------------------------- SearchParams plumbing
def test_search_params_no_retrace(small_nsg, ann_data):
    """patience/eps ride SearchParams as jit-static meta:
    repeats reuse the compiled beam, flips cost at most one compile."""
    idx = small_nsg
    q = ann_data["queries"][:8]
    sp = SearchParams(ef_search=24, patience=6, eps=0.0)
    idx.search(q, 10, sp)
    misses0 = beam_search._cache_size()
    for _ in range(3):
        idx.search(q, 10, sp)
    assert beam_search._cache_size() == misses0
    idx.search(q, 10, SearchParams(ef_search=24, patience=9))
    flipped = beam_search._cache_size()
    assert flipped <= misses0 + 1


def test_pipeline_adaptive_search_and_stats(small_nsg, ann_data):
    idx = small_nsg
    q = ann_data["queries"][:16]
    d, i = idx.search(q, 10, ef=32)
    base = idx.search_stats()
    d2, i2 = idx.search(q, 10, ef=32, patience=4)
    st = idx.search_stats()
    assert st["hops"] < base["hops"]
    assert 0 < st["active_fraction"] <= 1.0
    assert st["mean_hops"] > 0 and st["p99_hops"] >= st["mean_hops"]
    # recall sanity: the adaptive result still overlaps the exact one
    overlap = np.mean([len(set(a) & set(b)) / 10
                       for a, b in zip(np.asarray(i), np.asarray(i2))])
    assert overlap > 0.5


def test_recall_monotone_in_patience(small_nsg, ann_data):
    """More patience only lets lanes run longer, and pool merges only
    improve the top-k prefix — recall must be non-decreasing."""
    from repro.core import recall_at_k
    idx, q, ti = small_nsg, ann_data["queries"], ann_data["true_i"]
    recalls = [float(recall_at_k(
        idx.search(q, 10, SearchParams(ef_search=48, patience=p))[1], ti))
        for p in (2, 4, 8, 16)]
    assert all(a <= b + 1e-9 for a, b in zip(recalls, recalls[1:]))


# --------------------------------------------- factory / sharded plumbing
def test_factory_adapt_token(ann_data):
    data = ann_data["data"][:600]
    idx = build_index("NSG12,EP8,Adapt8", data, key=jax.random.PRNGKey(0))
    assert idx.params.patience == 8
    d, i = idx.search(ann_data["queries"][:8], 10)
    assert i.shape == (8, 10)
    with pytest.raises(ValueError, match="patience"):
        build_index("NSG12,Adapt0", data, key=jax.random.PRNGKey(0))
    # the compaction suffix left the grammar with its knob
    with pytest.raises(ValueError, match="Adapt8c16"):
        build_index("NSG12,EP8,Adapt8c16", data, key=jax.random.PRNGKey(0))


def test_build_index_adaptive_overrides(ann_data):
    data = ann_data["data"][:600]
    idx = build_index("NSG12,EP8", data, key=jax.random.PRNGKey(0),
                      patience=5, eps=0.01)
    assert idx.params.patience == 5
    assert idx.params.eps == pytest.approx(0.01)


def test_sharded_factory_threads_patience(ann_data):
    from repro.core.distributed import ShardedFactoryIndex
    idx = ShardedFactoryIndex("NSG8,EP2", n_shards=2, patience=6).fit(
        ann_data["data"][:400], key=jax.random.PRNGKey(0))
    assert all(s.params.patience == 6 for s in idx.subs)
    d, i = idx.search(ann_data["queries"][:4], 5)
    assert i.shape == (4, 5)


def test_default_space_has_patience():
    from repro.core.tuning.objective import default_space
    space = default_space(32, 2000)
    assert "patience" in space.names()


def test_search_params_space_has_patience(small_nsg):
    assert "patience" in small_nsg.search_params_space().names()


# ------------------------------------------------- IVFPQ m|dim validation
def test_ivfpq_m_must_divide_dim(ann_data):
    data = ann_data["data"][:600]           # dim = 32
    with pytest.raises(ValueError, match="must divide"):
        build_index("IVFPQ16x7", data)
    with pytest.raises(ValueError, match="must divide"):
        build_index("IVF16,PQ7", data)
    with pytest.raises(ValueError, match="must divide"):
        build_index("PQ7", data)
    with pytest.raises(ValueError, match="must divide"):
        build_index("NSG12,PQ7x8", data, key=jax.random.PRNGKey(0))
    idx = build_index("IVFPQ16x8", data)    # 8 | 32: fine
    d, i = idx.search(ann_data["queries"][:4], 5)
    assert i.shape == (4, 5)


def test_ivfpq_placeholder_parse_skips_validation():
    """The sharded wrapper probes search_params_space pre-fit with a
    placeholder dim — validation must wait for the real dim."""
    from repro.core.distributed import ShardedFactoryIndex
    ShardedFactoryIndex("IVFPQ16x7", n_shards=2).search_params_space()


# -------------------------------------------------- serve latency + stats
def test_microbatch_latency_stats(small_nsg, ann_data):
    from repro.serve.batching import MicroBatchQueue, pow2_buckets
    from repro.serve.serve_step import ann_search_step
    step = ann_search_step(small_nsg, k=5, buckets=pow2_buckets(16))
    queue = MicroBatchQueue(step, window_s=0.0)
    q = ann_data["queries"]
    t1 = queue.submit(q[:3])
    t2 = queue.submit(q[3:10])
    queue.flush()
    assert queue.take(t1)[1].shape == (3, 5)
    assert queue.take(t2)[1].shape == (7, 5)
    stats = queue.latency_stats()
    assert stats["served"] == 10 and stats["flushes"] == 1
    assert 0 < stats["p50_ms"] <= stats["p99_ms"]
    assert stats["mean_ms"] > 0
    assert 0 < stats["mean_occupancy"] <= 1.0   # 10 rows / 16-bucket pad
    # the serve step surfaces the index's traversal stats
    st = step.search_stats()
    assert st and st["hops"] > 0


# --------------------------------------------------- traffic savings model
def test_traversal_savings_report(small_nsg, ann_data):
    from repro.analysis.hop_traffic import traversal_savings_report
    idx = small_nsg
    q = ann_data["queries"][:16]
    idx.search(q, 10, ef=32)
    base = idx.search_stats()
    idx.search(q, 10, ef=32, patience=4)
    adapt = idx.search_stats()
    r = idx.graph.neighbors.shape[1]
    rep = traversal_savings_report(adapt, 32, r, idx.base.shape[1],
                                   baseline_stats=base)
    assert rep["launched_hops"] == rep["useful_hops"] + rep["wasted_hops"]
    assert rep["wasted_bytes"] == rep["wasted_hops"] * rep["bytes_per_hop"]
    assert rep["hop_reduction_vs_baseline"] > 1.0
    assert (rep["bytes_saved_vs_baseline"]
            == (rep["baseline_launched_hops"] - rep["launched_hops"])
            * rep["bytes_per_hop"])
    assert 0 < rep["active_fraction"] <= 1.0
