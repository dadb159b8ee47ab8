"""Quantized traversal subsystem: codecs, the lut_dist kernel, the
beam-search dist_backend switch, the exact-rerank tail, and the
rebuild-free codec reuse in the tuner."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FlatIndex, SearchParams, build_index, recall_at_k,
    structural_build_count,
)
from repro.core.beam_search import beam_search
from repro.core.quant import (
    Codec, Int8Codec, PQCodec, default_pq_m, make_codec,
)
from repro.kernels.lut_dist import lut_dist
from repro.kernels.lut_dist.lut_dist import lut_dist_pallas
from repro.kernels.lut_dist.ref import lut_dist_ref
from repro.kernels.row_gather import centroid_major

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def small_db():
    from repro.data import clustered_vectors, queries_like
    key = jax.random.PRNGKey(3)
    data = clustered_vectors(key, 800, 16, n_clusters=8)
    queries = queries_like(jax.random.PRNGKey(4), data, 48)
    _, true_i = FlatIndex(data).search(queries, 10)
    return data, queries, true_i


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------


def test_codec_protocol_conformance():
    key = jax.random.PRNGKey(0)
    data = jax.random.normal(key, (300, 16))
    for codec in (PQCodec(4, 32).fit(data, key=key),
                  Int8Codec().fit(data)):
        assert isinstance(codec, Codec)
        codes = codec.encode(data)
        assert codes.shape == (300, codec.code_bytes)
        assert codes.dtype == jnp.uint8
        lut = codec.lut(data[:5])
        assert lut.shape[0] == 5 and lut.shape[1] == codec.code_bytes
        assert codec.decode(codes).shape == data.shape
        assert codec.memory_bytes() > 0


def test_default_pq_m_divides():
    for dim in (96, 32, 48, 17, 7):
        m = default_pq_m(dim)
        assert 1 <= m and dim % m == 0
        if dim % 2 == 0:
            assert m == dim // 2     # even dims: 2-dim subspaces
    assert default_pq_m(96) == 48    # the paper-scale PQ48x8


def test_make_codec_dispatch():
    assert isinstance(make_codec("pq", 16, 4), PQCodec)
    assert make_codec("pq", 16, 0).m == default_pq_m(16)
    assert isinstance(make_codec("int8", 16), Int8Codec)
    with pytest.raises(ValueError, match="dist_backend"):
        make_codec("f32", 16)
    with pytest.raises(ValueError, match="divide"):
        PQCodec(5).fit(jax.random.normal(jax.random.PRNGKey(0), (50, 16)))


def test_int8_roundtrip_error_bound():
    """decode(encode(x)) is within half a quantization step per dim."""
    data = jax.random.normal(jax.random.PRNGKey(1), (400, 12)) * 3.0
    codec = Int8Codec().fit(data)
    err = jnp.abs(codec.decode(codec.encode(data)) - data)
    assert float(jnp.max(err / codec.scale[None])) <= 0.5 + 1e-4


def test_lut_agrees_with_decoded_distance():
    """sum_m lut[q, m, code[m]] == ||q - decode(code)||^2 (ADC identity)."""
    key = jax.random.PRNGKey(2)
    data = jax.random.normal(key, (300, 16))
    q = jax.random.normal(jax.random.PRNGKey(3), (6, 16))
    for codec in (PQCodec(8, 32).fit(data, key=key),
                  Int8Codec().fit(data)):
        codes = codec.encode(data)
        ids = jnp.arange(20, dtype=jnp.int32)[None, :].repeat(6, axis=0)
        adc = lut_dist_ref(codec.lut(q), codes, ids)
        dec = codec.decode(codes)
        exact = jnp.sum(
            (dec[ids] - q[:, None, :].astype(jnp.float32)) ** 2, axis=-1)
        np.testing.assert_allclose(np.asarray(adc), np.asarray(exact),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# kernels/lut_dist parity
# ---------------------------------------------------------------------------


# M off the 128-lane tile up to PQ300's 300 and int8's 600 at d'=600; a C
# that is not whole sublane tiles of 8 centroids
@pytest.mark.parametrize("m,c,r", [(4, 32, 9), (16, 256, 12), (1, 256, 5),
                                   (48, 256, 32), (300, 256, 32),
                                   (600, 256, 32), (5, 12, 7)])
def test_lut_dist_pallas_bit_exact(m, c, r):
    """The kernel, over the centroid-major table, equals the ref; codes
    take both ends of 0..c-1 in every row."""
    key = jax.random.PRNGKey(0)
    lut = jax.random.uniform(key, (7, m, c), dtype=jnp.float32) * 10
    codes = jax.random.randint(jax.random.PRNGKey(1), (200, m), 0, c)
    codes = codes.at[:, 0].set(0).at[:, -1].set(c - 1).astype(jnp.uint8)
    ids = jax.random.randint(jax.random.PRNGKey(2), (7, r), -1, 200)
    ref = np.asarray(lut_dist_ref(lut, codes, ids))
    pal = np.asarray(lut_dist_pallas(centroid_major(lut), codes, ids,
                                     interpret=True))
    np.testing.assert_array_equal(ref, pal)
    # padding convention: negative ids come back +inf in both
    assert np.isinf(ref[np.asarray(ids) < 0]).all()


def test_lut_dist_backend_dispatch():
    lut = jnp.ones((2, 4, 8))
    codes = jnp.zeros((10, 4), jnp.uint8)
    ids = jnp.zeros((2, 3), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(lut_dist(lut, codes, ids, backend="jnp")),
        np.asarray(lut_dist(centroid_major(lut), codes, ids,
                            backend="pallas")))
    with pytest.raises(ValueError, match="backend"):
        lut_dist(lut, codes, ids, backend="bogus")


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(m=st.integers(1, 8), r=st.integers(1, 16),
           seed=st.integers(0, 10**6))
    def test_lut_dist_parity_property(m, r, seed):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        lut = jax.random.uniform(k1, (3, m, 16), dtype=jnp.float32)
        codes = jax.random.randint(k2, (50, m), 0, 16).astype(jnp.uint8)
        ids = jax.random.randint(k3, (3, r), -1, 50)
        np.testing.assert_array_equal(
            np.asarray(lut_dist_ref(lut, codes, ids)),
            np.asarray(lut_dist_pallas(centroid_major(lut), codes, ids,
                                       interpret=True)))


# ---------------------------------------------------------------------------
# beam_search dist_backend switch
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_beam_search_quantized_requires_batched_and_codes(small_db):
    data, queries, _ = small_db
    idx = build_index("NSG12,EP4", data, key=jax.random.PRNGKey(0))
    q = queries[:4]
    entries = idx.eps.select(q)
    with pytest.raises(ValueError, match="codes"):
        beam_search(q, idx.base, idx.graph.neighbors, entries, ef=16, k=5,
                    dist_backend="pq")


@pytest.mark.slow
def test_quantized_beam_matches_adc_ranking(small_db):
    """The quantized beam's distances ARE lut_dist values of its ids."""
    data, queries, _ = small_db
    idx = build_index("NSG12,EP4,PQ8x8,Rerank0", data,
                      key=jax.random.PRNGKey(0))
    q = idx.project(queries[:8])
    lut = idx.codec.lut(q)
    d, i, _ = beam_search(q, idx.base, idx.graph.neighbors,
                          idx.eps.select(q), ef=32, k=10,
                          dist_backend="pq", codes=idx.codes, lut=lut)
    again = lut_dist_ref(lut, idx.codes, i)
    valid = np.asarray(i) >= 0
    np.testing.assert_allclose(np.asarray(d)[valid],
                               np.asarray(again)[valid], rtol=1e-6)


# ---------------------------------------------------------------------------
# end-to-end: factory grammar, rerank tail, recall
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_factory_grammar_quantized(small_db):
    data, _, _ = small_db
    idx = build_index("NSG12,EP4,PQ8x8,Rerank32", data,
                      key=jax.random.PRNGKey(0))
    assert idx.params.dist_backend == "pq"
    assert idx.params.pq_m == 8 and idx.params.rerank == 32
    assert isinstance(idx.codec, PQCodec) and idx.codes.dtype == jnp.uint8
    idx2 = build_index("NSG12,SQ8,Rerank16", data, key=jax.random.PRNGKey(0))
    assert idx2.params.dist_backend == "int8"
    assert isinstance(idx2.codec, Int8Codec)
    # rerank space only advertised once a codec is in play
    assert "rerank" in idx.search_params_space().names()
    assert "rerank" not in build_index(
        "NSG12", data, key=jax.random.PRNGKey(0)
    ).search_params_space().names()
    with pytest.raises(ValueError, match="trailing"):
        build_index("NSG12,Rerank32x8", data)


def test_quantized_examples_registered():
    from repro.core import available_factories
    nsg = available_factories()["NSG"]
    assert any("PQ" in s and "Rerank" in s for s in nsg)
    assert any("SQ8" in s for s in nsg)


@pytest.mark.slow
def test_rerank_recovers_f32_recall(small_db):
    """Acceptance: quantized recall@10 within 1pt of f32 at rerank=64."""
    data, queries, true_i = small_db
    sp = SearchParams(ef_search=64)
    f32 = build_index("NSG16,EP4", data, key=jax.random.PRNGKey(0))
    r_f32 = recall_at_k(f32.search(queries, 10, sp)[1], true_i)
    for spec in ("NSG16,EP4,PQ8x8,Rerank64", "NSG16,EP4,SQ8,Rerank64"):
        idx = build_index(spec, data, key=jax.random.PRNGKey(0))
        r_q = recall_at_k(idx.search(queries, 10, sp)[1], true_i)
        assert r_q >= r_f32 - 0.01, (spec, r_q, r_f32)


@pytest.mark.slow
def test_runtime_dist_backend_switch(small_db):
    """An f32-built index serves quantized via SearchParams alone."""
    data, queries, true_i = small_db
    idx = build_index("NSG16,EP4", data, key=jax.random.PRNGKey(0))
    assert idx.codec is None
    r = recall_at_k(idx.search(
        queries, 10, SearchParams(ef_search=64, dist_backend="pq",
                                  rerank=64))[1], true_i)
    assert idx.codec is not None         # lazily quantized once
    assert r >= 0.85
    # and back to f32 untouched
    r2 = recall_at_k(idx.search(queries, 10,
                                SearchParams(ef_search=64))[1], true_i)
    assert r2 >= 0.9


def test_rerank_zero_returns_adc_distances(small_db):
    data, queries, _ = small_db
    idx = build_index("NSG16,EP4,PQ8x8,Rerank0", data,
                      key=jax.random.PRNGKey(0))
    d, i = idx.search(queries, 10, SearchParams(ef_search=64))
    q = idx.project(queries)
    lut = idx.codec.lut(q)
    # internal ids == original ids here (no antihub subsampling)
    again = lut_dist_ref(lut, idx.codes, i)
    valid = np.asarray(i) >= 0
    np.testing.assert_allclose(np.asarray(d)[valid],
                               np.asarray(again)[valid], rtol=1e-6)


@pytest.mark.slow
def test_byte_traffic_reduction(small_db):
    """CPU stand-in for the >=2x QPS acceptance: per-hop bytes touched.

    An f32 hop gathers R rows of D*4 bytes; a quantized hop R rows of
    code_bytes. The ratio is the memory-bandwidth headroom the kernel
    exposes on real hardware.
    """
    data, _, _ = small_db
    for spec, floor in (("NSG16,EP4,PQ8x8,Rerank32", 8.0),
                        ("NSG16,EP4,SQ8,Rerank32", 4.0)):
        idx = build_index(spec, data, key=jax.random.PRNGKey(0))
        r = idx.graph.neighbors.shape[1]
        f32_hop = r * idx.base.shape[1] * idx.base.dtype.itemsize
        q_hop = r * idx.codes.shape[1] * idx.codes.dtype.itemsize
        assert f32_hop / q_hop >= floor >= 2.0, (spec, f32_hop, q_hop)


# ---------------------------------------------------------------------------
# memory accounting
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_memory_bytes_analytic(small_db):
    """Composed-index footprint must equal the analytic formula exactly."""
    data, _, _ = small_db
    idx = build_index("NSG12,EP4,PQ8x8,Rerank32", data,
                      key=jax.random.PRNGKey(0))
    n, d = idx.base.shape
    expected = (
        n * d * 4                                     # f32 vectors
        + idx.graph.neighbors.size * 4                # graph edges
        + idx.kept_idx.size * 4                       # id remap
        + idx.eps.centroids.size * 4 + idx.eps.member_ids.size * 4
        + n * idx.codec.m * 1                         # uint8 codes
        + idx.codec.codebooks.size * 4                # PQ codebooks
    )
    assert idx.memory_bytes() == expected
    # quantizing must ADD the codes+codebooks, not replace the vectors
    f32 = build_index("NSG12,EP4", data, key=jax.random.PRNGKey(0))
    assert idx.memory_bytes() > f32.memory_bytes()


def test_memory_bytes_composed_pca(small_db):
    data, _, _ = small_db
    idx = build_index("PCA8,NSG12,PQ4x8,Rerank16", data,
                      key=jax.random.PRNGKey(0))
    inner = idx.inner
    expected_inner = (
        inner.base.size * 4 + inner.graph.neighbors.size * 4
        + inner.kept_idx.size * 4
        + inner.eps.centroids.size * 4 + inner.eps.member_ids.size * 4
        + inner.codes.size + inner.codec.codebooks.size * 4)
    assert inner.memory_bytes() == expected_inner
    assert idx.memory_bytes() == expected_inner + (
        idx.pca.components.size + idx.pca.mean.size) * 4


# ---------------------------------------------------------------------------
# SearchParams staticness
# ---------------------------------------------------------------------------


def test_search_params_rerank_hashable_jit_static():
    a = SearchParams(ef_search=32, rerank=16)
    b = SearchParams(ef_search=32, rerank=16)
    assert hash(a) == hash(b) and a == b
    leaves, treedef = jax.tree_util.tree_flatten(a)
    assert leaves == []                  # all fields are static metadata

    traces = []

    @jax.jit
    def f(x, sp: SearchParams):
        traces.append(1)
        return x * (sp.rerank or 1)

    x = jnp.ones((3,))
    f(x, a)
    f(x, b)                              # equal params -> cache hit
    assert len(traces) == 1
    f(x, SearchParams(ef_search=32, rerank=32))   # static change: recompile
    assert len(traces) == 2
    f(x, dataclasses.replace(a, dist_backend="pq"))
    assert len(traces) == 3


def test_search_no_retrace_on_repeat(small_db):
    """Repeated quantized searches with identical static knobs reuse the
    compiled beam (the QPS-measurement property the tuner relies on)."""
    data, queries, _ = small_db
    idx = build_index("NSG12,EP4,PQ8x8,Rerank16", data,
                      key=jax.random.PRNGKey(0))
    sp = SearchParams(ef_search=32, rerank=16)
    idx.search(queries, 10, sp)
    misses0 = beam_search._cache_size()
    for _ in range(3):
        idx.search(queries, 10, sp)
    assert beam_search._cache_size() == misses0


# ---------------------------------------------------------------------------
# rerank monotonicity (hypothesis)
# ---------------------------------------------------------------------------


_RR_CACHE = {}


def _rr_fixture():
    """One tiny quantized NSG + oracle shared across hypothesis examples."""
    if not _RR_CACHE:
        from repro.data import clustered_vectors, queries_like
        data = clustered_vectors(jax.random.PRNGKey(30), 500, 16,
                                 n_clusters=8)
        queries = queries_like(jax.random.PRNGKey(31), data, 32)
        _, true_i = FlatIndex(data).search(queries, 10)
        _RR_CACHE["idx"] = build_index("NSG12,EP4,PQ8x8,Rerank32", data,
                                       key=jax.random.PRNGKey(32))
        _RR_CACHE["queries"] = queries
        _RR_CACHE["true_i"] = true_i
    return _RR_CACHE["idx"], _RR_CACHE["queries"], _RR_CACHE["true_i"]


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(rerank=st.integers(1, 32), mult=st.integers(2, 4))
    def test_recall_nondecreasing_in_rerank(rerank, mult):
        """A deeper exact tail rescores a superset of the shallower tail's
        beam survivors (the beam's ADC ranking is fixed at fixed ef), so
        recall@10 must not drop as rerank grows."""
        idx, queries, true_i = _rr_fixture()
        r_lo = recall_at_k(idx.search(
            queries, 10, SearchParams(ef_search=48, rerank=rerank))[1],
            true_i)
        r_hi = recall_at_k(idx.search(
            queries, 10,
            SearchParams(ef_search=48, rerank=rerank * mult))[1], true_i)
        assert r_hi >= r_lo


# ---------------------------------------------------------------------------
# tuner + sharding integration
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tuner_codec_rebuild_free(small_db):
    """dist_backend/rerank/alpha sweeps: ONE structural build, ONE codec
    training per (structure, backend) — codes shared across trials."""
    from repro.core.pipeline import IndexParams
    from repro.core.tuning import AnnObjective
    data, queries, _ = small_db
    base = IndexParams(pca_dim=data.shape[1], graph_degree=12,
                       build_knn_k=12, build_candidates=24, ef_search=32)
    obj = AnnObjective(data, queries, k=10, base_params=base, qps_repeats=1)
    b0 = structural_build_count()
    obj.evaluate({"dist_backend": "pq", "rerank": 16, "ef_search": 32})
    assert structural_build_count() == b0 + 1
    obj.evaluate({"dist_backend": "pq", "rerank": 64, "alpha": 1.1})
    obj.evaluate({"dist_backend": "int8", "rerank": 16})
    obj.evaluate({"ef_search": 64})                     # plain f32 trial
    assert structural_build_count() == b0 + 1           # still one build
    assert len(obj._codec_cache) == 2                   # pq + int8, once
    recs = [r.recall for _, r in obj.eval_log]
    assert all(r >= 0.8 for r in recs), recs


def test_default_space_quantized_knobs(small_db):
    from repro.core.tuning import default_space
    names = default_space(16, 800, quantized=True).names()
    assert "dist_backend" in names and "rerank" in names
    assert "dist_backend" not in default_space(16, 800).names()


@pytest.mark.slow
def test_sharded_quantized(small_db):
    from repro.core.distributed import ShardedFactoryIndex
    data, queries, true_i = small_db
    idx = ShardedFactoryIndex("NSG12,EP4,PQ8x8,Rerank32", n_shards=2).fit(
        data, key=jax.random.PRNGKey(0))
    for s in idx.subs:
        assert s.codes is not None       # per-shard codes, per-shard codecs
    r = recall_at_k(idx.search(queries, 10,
                               SearchParams(ef_search=64))[1], true_i)
    assert r >= 0.85
    assert idx.memory_bytes() >= sum(s.memory_bytes() for s in idx.subs)


@pytest.mark.slow
def test_sharded_reprune_keeps_quantized_codes(small_db):
    """Sharded reprune x quantized serving: deriving an (alpha, degree)
    variant must not re-encode — per-shard codes/codecs are shared with
    the parent (same objects), stay equal to a fresh encode of the shard
    base, and the derived index still serves the quantized+rerank path."""
    from repro.core.distributed import ShardedFactoryIndex
    data, queries, true_i = small_db
    idx = ShardedFactoryIndex("NSG12,EP4,PQ8x8,Rerank32", n_shards=2).fit(
        data, key=jax.random.PRNGKey(0))
    b0 = structural_build_count()
    der = idx.reprune(alpha=1.1, degree=8)
    assert structural_build_count() == b0, "reprune must not rebuild"
    for sub, dsub in zip(idx.subs, der.subs):
        assert dsub.codes is sub.codes, "reprune re-encoded the shard"
        assert dsub.codec is sub.codec
        assert dsub.graph.neighbors.shape[1] == 8
        # rerank parity: the shared codes ARE the fresh-encoded baseline
        np.testing.assert_array_equal(
            np.asarray(dsub.codes),
            np.asarray(dsub.codec.encode(dsub.base)))
    r = recall_at_k(der.search(queries, 10,
                               SearchParams(ef_search=64))[1], true_i)
    assert r >= 0.8


# ---------------------------------------------------------------------------
# PQ dedup (satellite 1)
# ---------------------------------------------------------------------------


def test_pqindex_delegates_to_codec_bit_identical():
    """core/pq.py is a view over core.quant.PQCodec: same codebooks, same
    codes, and search equal to the pre-dedup ADC formula."""
    key = jax.random.PRNGKey(7)
    data = jax.random.normal(key, (400, 16))
    q = jax.random.normal(jax.random.PRNGKey(8), (9, 16))
    from repro.core.pq import PQIndex
    idx = PQIndex(m=4, n_centroids=32).fit(data, key=key)
    codec = PQCodec(4, 32).fit(data, key=key)
    np.testing.assert_array_equal(np.asarray(idx.codebooks),
                                  np.asarray(codec.codebooks))
    np.testing.assert_array_equal(np.asarray(idx.codes),
                                  np.asarray(codec.codes))
    assert idx.codes.dtype == jnp.uint8

    # the pre-dedup `_pq_search`, verbatim (jitted whole, as it was — the
    # fusion boundaries matter for bit-equality)
    import functools

    @functools.partial(jax.jit, static_argnames=("k",))
    def old_pq_search(queries, codebooks, codes, k):
        qn, d = queries.shape
        m, c, dsub = codebooks.shape
        qsub = queries.reshape(qn, m, dsub).astype(jnp.float32)
        diff = qsub[:, :, None, :] - codebooks[None].astype(jnp.float32)
        lut = jnp.sum(diff * diff, axis=-1)
        dist = jnp.sum(jnp.take_along_axis(
            lut[:, None, :, :], codes[None, :, :, None], axis=3)[..., 0],
            axis=2)
        nd, ids = jax.lax.top_k(-dist, k)
        return -nd, ids

    d, i = idx.search(q, 5)
    d_old, i_old = old_pq_search(q, codec.codebooks,
                                 codec.codes.astype(jnp.int32), 5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_old))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_old))


@pytest.mark.parametrize("n,d,m,c", [(1200, 32, 16, 64), (600, 600, 300, 256)])
def test_pq_fit_is_one_kmeans_per_subspace(n, d, m, c):
    """PQCodec.fit trains every sub-space in one program: the codebooks of
    a k-means per sub-space (key fold_in(key, j)) run one after another,
    and the codes those codebooks encode."""
    from repro.core.kmeans import kmeans
    key = jax.random.PRNGKey(7)
    data = jax.random.normal(jax.random.PRNGKey(n), (n, d)) \
        * jnp.linspace(1.0, 0.1, d)
    codec = PQCodec(m, c).fit(data, key=key, iters=4)
    sub = data.reshape(n, m, d // m)
    looped = PQCodec(m, c)
    looped.codebooks = jnp.stack([
        kmeans(jax.random.fold_in(key, j), sub[:, j], c, iters=4).centroids
        for j in range(m)])
    np.testing.assert_allclose(np.asarray(codec.codebooks),
                               np.asarray(looped.codebooks), rtol=0,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(codec.codes),
                                  np.asarray(looped.encode(data)))
    assert codec.codes.dtype == jnp.uint8 and codec.codes.shape == (n, m)


def test_ivfpq_still_composes():
    """IVF-PQ reads pq.codebooks/pq.codes — the delegation must keep it."""
    data = jax.random.normal(jax.random.PRNGKey(9), (600, 16))
    q = jax.random.normal(jax.random.PRNGKey(10), (8, 16))
    idx = build_index("IVFPQ16x8", data, key=jax.random.PRNGKey(0))
    d, i = idx.search(q, 5, SearchParams(nprobe=8))
    assert d.shape == i.shape == (8, 5)
    assert int(np.asarray(i).max()) < 600


# ---------------------------------------------------------------------------
# pinned 20k acceptance set (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_quantized_recall_acceptance_20k():
    """Acceptance: on the pinned 20k set, PQ+Rerank64 recall@10 within 1pt
    of the f32 NSG twin at matched ef, with >=2x per-hop byte reduction."""
    from repro.data import clustered_vectors, queries_like
    data = clustered_vectors(jax.random.PRNGKey(0), 20000, 16, n_clusters=32)
    queries = queries_like(jax.random.PRNGKey(1), data, 96)
    _, true_i = FlatIndex(data).search(queries, 10)
    sp = SearchParams(ef_search=64)
    f32 = build_index("NSG16,EP8", data, key=jax.random.PRNGKey(2))
    r_f32 = recall_at_k(f32.search(queries, 10, sp)[1], true_i)
    pq = build_index("NSG16,EP8,PQ8x8,Rerank64", data,
                     key=jax.random.PRNGKey(2))
    r_pq = recall_at_k(pq.search(queries, 10, sp)[1], true_i)
    assert r_f32 >= 0.93
    assert r_pq >= r_f32 - 0.01, (r_pq, r_f32)
    hop_ratio = (pq.base.shape[1] * 4) / pq.codes.shape[1]
    assert hop_ratio >= 2.0
