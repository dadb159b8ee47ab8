"""The quantized deployment through the normal serve path, against plain
brute force: the tuned pipeline built with ``dist_backend="pq"`` by
``launch/serve.build_ann_index`` and served by ``ann_search_step`` behind a
``MicroBatchQueue``. Its recall stays near the f32 traversal of the same
build, each distance it reports is the exact PCA-space distance of its id
(the rerank is exact), the fused and staged hops agree, and a restored
snapshot serves the same answers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ANNConfig
from repro.core import SearchParams, load_index, save_index
from repro.data import clustered_vectors, queries_like
from repro.launch.serve import build_ann_index
from repro.serve.batching import MicroBatchQueue, pow2_buckets
from repro.serve.serve_step import ann_search_step

N, DIM, PCA_DIM, K, BATCH = 3000, 64, 48, 10, 64
CFG = ANNConfig(name="ann-pq-small", dim=DIM, n_database=N, k=K,
                pca_dim=PCA_DIM, antihub_keep=0.9, ep_clusters=8,
                ef_search=32, graph_degree=16, build_knn_k=16,
                build_candidates=32, knn_backend="exact",
                dist_backend="pq", pq_m=0, rerank=32, hop_backend="staged")


@pytest.fixture(scope="module")
def built():
    data = clustered_vectors(jax.random.PRNGKey(3), N, DIM, n_clusters=16)
    queries = np.asarray(queries_like(jax.random.PRNGKey(4), data, 200))
    index = build_ann_index(CFG, data, jax.random.PRNGKey(0))
    return data, queries, index


def _brute_force(data, queries):
    """Exact L2 top-k over the raw rows, plain jax.numpy at HIGHEST."""
    with jax.default_matmul_precision("highest"):
        q, x = jnp.asarray(queries), jnp.asarray(data)
        d = (jnp.sum(q * q, 1)[:, None] + jnp.sum(x * x, 1)[None, :]
             - 2.0 * q @ x.T)
    return np.asarray(jax.lax.top_k(-d, K)[1])


def _serve(index, queries, **params):
    """Every query through the queue in requests of up to BATCH rows."""
    step = ann_search_step(index, K, SearchParams(ef_search=32, **params),
                           buckets=pow2_buckets(BATCH))
    queue = MicroBatchQueue(step, window_s=0.0)
    tickets = [queue.submit(queries[i:i + n]) for i, n in
               ((0, 64), (64, 37), (101, 64), (165, 35))]
    queue.flush()
    out = [queue.take(t) for t in tickets]
    return (np.concatenate([np.asarray(d) for d, _ in out]),
            np.concatenate([np.asarray(i) for _, i in out]))


def _recall(ids, truth):
    return float(np.mean([len(set(a) & set(b)) / K
                          for a, b in zip(ids, truth)]))


def test_pq_build_is_quantized_with_the_auto_rule(built):
    _, _, index = built
    assert index.codec_backend == "pq"
    assert index.codec.m == PCA_DIM // 2            # default_pq_m(48)
    assert index.codes.shape == (index.base.shape[0], PCA_DIM // 2)
    assert index.codes.dtype == jnp.uint8


def test_pq_recall_within_002_of_f32_traversal(built):
    data, queries, index = built
    truth = _brute_force(data, queries)
    _, pq_ids = _serve(index, queries, dist_backend="pq", rerank=32)
    _, f32_ids = _serve(index, queries, dist_backend="f32")
    pq, f32 = _recall(pq_ids, truth), _recall(f32_ids, truth)
    assert pq >= f32 - 0.02, (pq, f32)
    assert pq >= 0.9


def test_pq_distances_are_exact_pca_distances(built):
    """The rerank rescores in f32: a reported distance is its id's squared
    distance in PCA space, to float32 rounding."""
    data, queries, index = built
    d, ids = _serve(index, queries, dist_backend="pq", rerank=32)
    assert (ids >= 0).all()
    internal = np.full(N, -1)
    internal[np.asarray(index.kept_idx)] = np.arange(index.base.shape[0])
    assert (internal[ids] >= 0).all()               # AntiHub kept them
    q = np.asarray(index.project(jnp.asarray(queries)), np.float64)
    rows = np.asarray(index.base, np.float64)[internal[ids]]
    exact = np.sum((rows - q[:, None, :]) ** 2, axis=-1)
    np.testing.assert_allclose(d, exact, rtol=1e-5, atol=1e-5)


def test_pq_fused_and_staged_hops_return_the_same_answers(built):
    _, queries, index = built
    fused = _serve(index, queries[:101], dist_backend="pq", rerank=32,
                   hop_backend="fused")
    staged = _serve(index, queries[:101], dist_backend="pq", rerank=32,
                    hop_backend="staged")
    np.testing.assert_array_equal(fused[1], staged[1])
    np.testing.assert_array_equal(fused[0], staged[0])


def test_pq_snapshot_round_trip_serves_identical_answers(built, tmp_path):
    _, queries, index = built
    save_index(index, str(tmp_path / "pq"))
    restored = load_index(str(tmp_path / "pq"))
    assert restored.codec_backend == "pq"
    np.testing.assert_array_equal(np.asarray(restored.codes),
                                  np.asarray(index.codes))
    want = _serve(index, queries, dist_backend="pq", rerank=32)
    got = _serve(restored, queries, dist_backend="pq", rerank=32)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[0], want[0])


def test_pq_without_rerank_reports_adc_distances(built):
    """rerank=0 hands back the traversal's ADC distances, which are not
    the exact ones: what the benchmark's check has to catch."""
    _, queries, index = built
    d0, _ = _serve(index, queries, dist_backend="pq", rerank=0)
    d, _ = _serve(index, queries, dist_backend="pq", rerank=32)
    assert not np.allclose(d0, d, rtol=1e-3)
