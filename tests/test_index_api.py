"""Contract tests for the unified Index protocol + factory registry.

Every registered spec must: build from a string on synthetic data, conform
to the ``Index`` protocol, search with default AND overridden
``SearchParams`` through the one generic code path, return valid ids, and
beat a spec-specific recall floor against the ``FlatIndex`` oracle.
"""
import jax
import numpy as np
import pytest

from repro.core import (
    FlatIndex, Index, SearchParams, available_factories, build_index,
    list_index_specs, recall_at_k,
)
from repro.core.index_api import parse_spec
from repro.core.tuning import SearchParamsObjective, Study, TPESampler
from repro.core.tuning.space import SearchSpace


@pytest.fixture(scope="module")
def small_db():
    """Small enough that the sequential HNSW build stays in seconds."""
    from repro.data import clustered_vectors, queries_like
    key = jax.random.PRNGKey(7)
    data = clustered_vectors(key, 600, 32, n_clusters=8)
    queries = queries_like(jax.random.PRNGKey(8), data, 24)
    _, true_i = FlatIndex(data).search(queries, 10)
    return data, queries, true_i


def recall_floor(spec: str) -> float:
    """Per-family recall@10 floor vs the brute-force oracle on small_db.

    The regression net: a traversal/build change that degrades any family
    below its floor fails here, not in a benchmark nobody re-ran.
    """
    if spec.startswith("PCA"):              # paper's d' reduction is lossy
        return 0.55 if spec == "PCA24,Flat" else 0.50
    if spec == "Flat":
        return 0.999
    if "Rerank" in spec:                    # quantized beam + exact tail
        return 0.85                         # rerank recovers ADC's loss
    if "PQ" in spec:                        # quantization caps recall
        return 0.30
    if "AH" in spec:                        # subsampling drops true hits
        return 0.80
    if spec.startswith("IVF"):
        return 0.85
    return 0.90                             # graph families (HNSW, NSG)


# Every registered family's example specs (the registry is the single
# enumeration point — a new register_index with examples lands here
# automatically), plus PCA-prefixed composition for each kind.
MAXED = SearchParams(ef_search=128, nprobe=16)
SPECS = [s for examples in available_factories().values() for s in examples]
SPECS += ["PCA24,Flat", "PCA24,IVF16", "PCA24,HNSW8",
          # the full PCA+NSG+EP composition is a sequential graph build
          # (~30s on CPU) — slow lane; the bare NSG specs keep fast-lane
          # family coverage
          pytest.param("PCA24,NSG12,EP8", marks=pytest.mark.slow,
                       id="PCA24,NSG12,EP8")]


def test_regression_net_covers_all_families():
    fams = available_factories()
    assert set(fams) >= {"Flat", "IVF", "IVFPQ", "PQ", "HNSW", "NSG"}
    assert "HNSW8,EP8" in fams["HNSW"]          # paper §3.1 EP knob on HNSW
    assert "NSG12,AH0.9,EP8" in fams["NSG"]     # full paper pipeline
    for name, examples in fams.items():
        assert examples, f"family {name} registered without example specs"


@pytest.mark.parametrize("spec", SPECS)
def test_spec_contract(spec, small_db):
    data, queries, true_i = small_db
    floor = recall_floor(spec)
    idx = build_index(spec, data, key=jax.random.PRNGKey(0))
    assert isinstance(idx, Index)
    assert idx.spec == spec
    assert 0 < idx.ntotal <= data.shape[0]
    assert idx.dim == data.shape[1]
    assert isinstance(idx.search_params_space(), SearchSpace)

    # default params
    d, i = idx.search(queries, 10)
    assert d.shape == i.shape == (queries.shape[0], 10)
    assert int(np.asarray(i).max()) < data.shape[0]
    assert recall_at_k(i, true_i) >= floor

    # overridden SearchParams go through the same call, no refit
    d2, i2 = idx.search(queries, 10, MAXED)
    assert recall_at_k(i2, true_i) >= floor


def test_params_change_behavior_without_refit(small_db):
    data, queries, true_i = small_db
    idx = build_index("IVF16", data)
    r1 = recall_at_k(idx.search(queries, 10, SearchParams(nprobe=1))[1],
                     true_i)
    r16 = recall_at_k(idx.search(queries, 10, SearchParams(nprobe=16))[1],
                      true_i)
    assert r1 <= r16
    assert r16 >= 0.999          # probing every list is exact


@pytest.mark.slow
def test_generic_tuner_is_index_agnostic(small_db):
    """Acceptance: one tuner code path optimizes SearchParams for multiple
    factory specs — zero index-specific branches on the caller side."""
    data, queries, _ = small_db
    for spec in ("NSG12,EP4", "IVF16"):
        obj = SearchParamsObjective(spec, data, queries, k=10,
                                    recall_floor=0.8, qps_repeats=1)
        assert len(obj.space.names()) >= 1
        study = Study(obj.space, TPESampler(seed=0, n_startup=2))
        study.optimize(obj.single_objective, n_trials=4)
        best = study.best_trial
        assert best.feasible
        assert set(best.params) <= {"ef_search", "nprobe", "mode",
                                    "chunk", "patience"}


@pytest.mark.slow
def test_sharded_factory_index(small_db):
    from repro.core.distributed import ShardedFactoryIndex
    data, queries, true_i = small_db
    idx = ShardedFactoryIndex("NSG12,EP4", n_shards=3).fit(data)
    assert isinstance(idx, Index)
    assert idx.ntotal == data.shape[0]
    d, i = idx.search(queries, 10, SearchParams(ef_search=64))
    assert recall_at_k(i, true_i) >= 0.9
    # global ids must cover rows beyond the first shard's range
    assert int(np.asarray(i).max()) >= data.shape[0] // 3


def test_sharded_factory_index_shares_pca_projection(small_db):
    """A PCA prefix must be fit once globally: per-shard projections would
    merge distances from different subspaces. With exact shards, sharded
    search must match the unsharded index id-for-id."""
    from repro.core.distributed import ShardedFactoryIndex
    data, queries, _ = small_db
    sharded = ShardedFactoryIndex("PCA24,Flat", n_shards=3).fit(data)
    whole = build_index("PCA24,Flat", data)
    _, i_sharded = sharded.search(queries, 10)
    _, i_whole = whole.search(queries, 10)
    assert (np.sort(np.asarray(i_sharded), 1)
            == np.sort(np.asarray(i_whole), 1)).all()


def test_registry_errors():
    data = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    with pytest.raises(ValueError, match="no registered index"):
        build_index("Bogus32", data)
    with pytest.raises(ValueError, match="trailing tokens"):
        build_index("Flat,Flat", data)
    with pytest.raises(ValueError, match="PCA prefix but no index"):
        build_index("PCA8", data)
    assert set(list_index_specs()) >= {"Flat", "IVF", "IVFPQ", "PQ", "HNSW",
                                       "NSG"}


def test_parse_spec_defers_fit():
    pca_dim, idx = parse_spec("PCA8,NSG16,EP4", dim=32)
    assert pca_dim == 8
    assert idx.params.pca_dim == 8          # NSG builds in the reduced space
    assert idx.params.ep_clusters == 4


def test_custom_registration_round_trips(small_db):
    from repro.core import register_index

    class DoubleFlat(FlatIndex):
        """Toy custom family: proves third-party indexes are one decorator."""

    @register_index("DoubleFlat", r"^DoubleFlat$")
    def _build(m, rest, dim):
        return DoubleFlat(), 0

    data, queries, true_i = small_db
    idx = build_index("DoubleFlat", data)
    assert recall_at_k(idx.search(queries, 10)[1], true_i) >= 0.999


# --------------------------------------------------------- HNSW serve path


@pytest.fixture(scope="module")
def hnsw_idx(small_db):
    data, _, _ = small_db
    return build_index("HNSW8", data, key=jax.random.PRNGKey(0))


def test_hnsw_descent_is_batched_device_call(hnsw_idx, small_db):
    """Upper-layer descent runs as ONE vmapped jit call for the whole batch
    and lands on the same layer-0 entries as the host greedy reference."""
    _, queries, _ = small_db
    entries = hnsw_idx.entry_points(queries)
    assert isinstance(entries, jax.Array)
    assert entries.shape == (queries.shape[0],)
    qn = np.asarray(queries, np.float32)
    host = np.empty(qn.shape[0], np.int32)
    for qi in range(qn.shape[0]):           # the loop the device path killed
        cur = hnsw_idx.entry
        for l in range(int(hnsw_idx.node_level[hnsw_idx.entry]), 0, -1):
            if l < len(hnsw_idx.layers):
                cur = hnsw_idx._greedy(qn[qi], cur, hnsw_idx.layers[l])
        host[qi] = cur
    # identical up to distance ties (matmul vs direct squared-diff rounding)
    assert (np.asarray(entries) == host).mean() >= 0.95


def test_hnsw_upper_table_is_device_resident(hnsw_idx):
    layers = hnsw_idx.layers
    assert hnsw_idx._upper.shape == (len(layers) - 1,) + layers[1].shape
    for li, layer in enumerate(layers[1:]):
        assert (np.asarray(hnsw_idx._upper[li]) == layer).all()


def test_hnsw_search_passes_mode_through(hnsw_idx, small_db, monkeypatch):
    """SearchParams.mode must reach beam_search (regression: was dropped)."""
    import repro.core.hnsw as hnsw_mod
    _, queries, _ = small_db
    seen = {}
    orig = hnsw_mod.beam_search

    def spy(*args, **kw):
        seen.update(kw)
        return orig(*args, **kw)

    monkeypatch.setattr(hnsw_mod, "beam_search", spy)
    hnsw_idx.search(queries, 5, SearchParams(mode="fori", ef_search=32))
    assert seen["mode"] == "fori"
    assert seen["ef"] == 32
    assert "gather_dist" not in seen       # the platform's own arithmetic


def test_hnsw_ep_spec_replaces_hierarchy(small_db):
    data, queries, true_i = small_db
    idx = build_index("HNSW8,EP8", data, key=jax.random.PRNGKey(0))
    assert idx.eps is not None and idx.eps.n_clusters == 8
    entries = np.asarray(idx.entry_points(queries))
    assert set(entries) <= set(np.asarray(idx.eps.member_ids))
    assert recall_at_k(idx.search(queries, 10)[1], true_i) >= 0.90


def test_recall_at_k_divides_by_requested_k():
    """A wider (distance-ascending) oracle changes neither numerator nor
    denominator: only its first k columns count as the true set."""
    import jax.numpy as jnp
    true = jnp.array([[1, 2, 3, 4, 5, 6]])
    assert recall_at_k(jnp.array([[1, 2, 3]]), true) == 1.0
    # ids ranked 4-6 by the oracle are NOT in the true top-3
    assert recall_at_k(jnp.array([[4, 5, 6]]), true) == 0.0
    assert recall_at_k(jnp.array([[1, 2, 9]]), true) == pytest.approx(2 / 3)
