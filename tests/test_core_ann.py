"""Unit tests for the paper's core pipeline components."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FlatIndex, IndexParams, TunedGraphIndex, recall_at_k
from repro.core.antihub import antihub_keep_indices, k_occurrence
from repro.core.beam_search import beam_search
from repro.core.distances import l2_topk, pairwise_sqdist
from repro.core.entry_points import fit_entry_points
from repro.core.kmeans import kmeans
from repro.core.knn_graph import knn_graph
from repro.core.nsg import build_nsg, mrng_prune
from repro.core.pca import dim_for_energy, fit_pca


# ---------------------------------------------------------------- distances
def test_pairwise_sqdist_matches_naive():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (7, 13))
    x = jax.random.normal(jax.random.PRNGKey(1), (29, 13))
    got = pairwise_sqdist(q, x)
    want = ((np.asarray(q)[:, None, :] - np.asarray(x)[None]) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,chunk", [(100, 32), (128, 128), (65, 64)])
def test_l2_topk_exact(n, chunk):
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (9, 8))
    x = jax.random.normal(jax.random.PRNGKey(3), (n, 8))
    d, i = l2_topk(q, x, 5, chunk=chunk)
    full = np.asarray(pairwise_sqdist(q, x))
    want_i = np.argsort(full, axis=1)[:, :5]
    want_d = np.take_along_axis(full, want_i, axis=1)
    np.testing.assert_allclose(np.asarray(d), want_d, rtol=1e-4, atol=1e-4)
    # ids may tie-swap; compare distance sets
    got_d = np.take_along_axis(full, np.asarray(i), axis=1)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)


def test_l2_topk_ascending_and_ids_valid():
    q = jax.random.normal(jax.random.PRNGKey(4), (3, 6))
    x = jax.random.normal(jax.random.PRNGKey(5), (50, 6))
    d, i = l2_topk(q, x, 10, chunk=16)
    d = np.asarray(d)
    assert (np.diff(d, axis=1) >= -1e-6).all()
    assert ((np.asarray(i) >= 0) & (np.asarray(i) < 50)).all()


# ---------------------------------------------------------------------- pca
def test_pca_reconstruction_improves_with_dim():
    x = jax.random.normal(jax.random.PRNGKey(6), (300, 24))
    x = x * (0.8 ** jnp.arange(24))[None, :]
    errs = []
    for d in (4, 12, 24):
        p = fit_pca(x, d)
        rec = p.inverse_transform(p.transform(x))
        errs.append(float(jnp.mean((rec - x) ** 2)))
    assert errs[0] > errs[1] > errs[2]
    assert errs[2] < 1e-6  # full-dim is lossless


def test_pca_preserves_distances_at_full_dim(ann_data):
    p = fit_pca(ann_data["data"], ann_data["data"].shape[1])
    z = p.transform(ann_data["data"][:50])
    dz = pairwise_sqdist(z[:10], z)
    dx = pairwise_sqdist(ann_data["data"][:10], ann_data["data"][:50])
    np.testing.assert_allclose(np.asarray(dz), np.asarray(dx), rtol=1e-3,
                               atol=1e-3)


def test_dim_for_energy_monotone():
    x = jax.random.normal(jax.random.PRNGKey(7), (200, 16))
    x = x * (0.7 ** jnp.arange(16))[None, :]
    assert dim_for_energy(x, 0.5) <= dim_for_energy(x, 0.9) <= 16


# ------------------------------------------------------------------- kmeans
def test_kmeans_inertia_beats_random_assignment():
    x = jax.random.normal(jax.random.PRNGKey(8), (400, 8))
    km = kmeans(jax.random.PRNGKey(9), x, 8, iters=8)
    base = float(jnp.mean(jnp.sum((x - x.mean(0)) ** 2, -1)))
    assert float(km.inertia) < base
    assert km.centroids.shape == (8, 8)
    assert int(km.assignments.max()) < 8


def test_kmeans_k_equals_one_is_mean():
    x = jax.random.normal(jax.random.PRNGKey(10), (100, 4))
    km = kmeans(jax.random.PRNGKey(11), x, 1, iters=3)
    np.testing.assert_allclose(np.asarray(km.centroids[0]),
                               np.asarray(x.mean(0)), atol=1e-4)


# ------------------------------------------------------------------ antihub
def test_k_occurrence_sums_to_nk(ann_data):
    occ = k_occurrence(ann_data["data"][:200], k=5)
    assert int(occ.sum()) == 200 * 5


def test_antihub_keeps_hubs(ann_data):
    data = ann_data["data"][:300]
    occ = np.asarray(k_occurrence(data, k=10))
    kept = np.asarray(antihub_keep_indices(data, 0.7, k=10))
    assert len(kept) == 210
    removed = np.setdiff1d(np.arange(300), kept)
    assert occ[kept].min() >= occ[removed].max() - 1  # ties allowed
    assert (np.diff(kept) > 0).all()


def test_antihub_keep_all():
    data = jax.random.normal(jax.random.PRNGKey(12), (50, 4))
    kept = antihub_keep_indices(data, 1.0)
    assert (np.asarray(kept) == np.arange(50)).all()


# ---------------------------------------------------------------- knn graph
def test_knn_graph_excludes_self_and_is_exact(ann_data):
    data = ann_data["data"][:150]
    d, i = knn_graph(data, 5, query_chunk=64, db_chunk=64)
    i = np.asarray(i)
    assert (i != np.arange(150)[:, None]).all()
    full = np.array(pairwise_sqdist(data, data))
    np.fill_diagonal(full, np.inf)
    want = np.sort(full, axis=1)[:, :5]
    np.testing.assert_allclose(np.sort(np.asarray(d), 1), want, rtol=1e-3,
                               atol=1e-3)


# -------------------------------------------------------------------- beam
def test_beam_search_on_full_graph_is_exact(ann_data):
    """With the complete graph, one expansion reaches everything."""
    data = ann_data["data"][:100]
    q = ann_data["queries"][:8]
    nbrs = jnp.tile(jnp.arange(100, dtype=jnp.int32)[None, :], (100, 1))
    entry = jnp.zeros((8,), jnp.int32)
    d, i, _ = beam_search(q, data, nbrs, entry, ef=100, k=5)
    td, ti = FlatIndex(data).search(q, 5)
    assert recall_at_k(i, ti) == 1.0


@pytest.mark.parametrize("arith", ["diffsq", "dot"])
@pytest.mark.parametrize("patience", [None, 4])
@pytest.mark.parametrize("mode", ["while", "fori"])
def test_lanes_independent(small_nsg, ann_data, mode, patience, arith):
    """Lanes never interact: a query's answer in a 48-query batch is its
    answer searched alone. Under the kernel family's diff-square
    (``gather_backend="jnp"``) ids, distances and hops are bit-equal. The
    dot formula |q|^2 + |x|^2 - 2 q.x rounds its f32 matmul by batch shape,
    so there ids and hops are equal and distances agree to 1e-6 of
    |q|^2 + |x|^2, the scale its cancellation error lives at."""
    idx = small_nsg
    q = idx.project(ann_data["queries"])
    e = idx.eps.select(q)
    kw = dict(ef=48, k=10, max_iters=192, mode=mode, patience=patience,
              gather_backend="jnp" if arith == "diffsq" else None)
    args = (idx.base, idx.graph.neighbors)
    db_, ib, hb = beam_search(q, *args, e, **kw)
    alone = [beam_search(q[j:j + 1], *args, e[j:j + 1], **kw)
             for j in range(q.shape[0])]
    da, ia, ha = (np.concatenate([np.asarray(r[n]) for r in alone])
                  for n in range(3))
    np.testing.assert_array_equal(np.asarray(ib), ia)
    np.testing.assert_array_equal(np.asarray(hb), ha)
    if arith == "diffsq":
        np.testing.assert_array_equal(np.asarray(db_), da)
    else:
        scale = (np.sum(np.asarray(q) ** 2, -1)[:, None]
                 + np.sum(np.asarray(idx.base) ** 2, -1)[ia])
        assert np.all(np.abs(np.asarray(db_) - da) <= 1e-6 * scale)


def test_beam_modes_agree(small_nsg, ann_data):
    idx = small_nsg
    q = idx.project(ann_data["queries"])
    e = idx.eps.select(q)
    d1, i1, _ = beam_search(q, idx.base, idx.graph.neighbors, e, ef=48, k=10,
                            max_iters=192, mode="while")
    d2, i2, _ = beam_search(q, idx.base, idx.graph.neighbors, e, ef=48, k=10,
                            max_iters=192, mode="fori")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


# --------------------------------------------------------------------- nsg
def test_mrng_prune_keeps_nearest_and_no_dups():
    data = jax.random.normal(jax.random.PRNGKey(13), (64, 8))
    cand = jnp.tile(jnp.arange(1, 33, dtype=jnp.int32)[None], (4, 1))
    node = jnp.arange(4, dtype=jnp.int32) * 40
    from repro.core.nsg import pairwise_rows_sqdist
    cd = pairwise_rows_sqdist(data[node], data, cand)
    order = jnp.argsort(cd, 1)
    cand = jnp.take_along_axis(cand, order, 1)
    cd = jnp.take_along_axis(cd, order, 1)
    out = np.asarray(mrng_prune(data, node, cand, cd, degree=8))
    for row, p in zip(out, np.asarray(node)):
        vals = row[row >= 0]
        assert len(np.unique(vals)) == len(vals)
        assert p not in vals
        assert len(vals) >= 1
        # nearest candidate always survives MRNG
        assert vals[0] == np.asarray(cand)[0 if p == 0 else list(node).index(p)][0]


def test_nsg_fully_reachable(small_nsg):
    nbrs = np.asarray(small_nsg.graph.neighbors)
    n = nbrs.shape[0]
    seen = np.zeros(n, bool)
    stack = [int(small_nsg.graph.medoid)]
    seen[stack[0]] = True
    while stack:
        u = stack.pop()
        for v in nbrs[u]:
            if v >= 0 and not seen[v]:
                seen[v] = True
                stack.append(int(v))
    assert seen.all()


def test_nsg_recall(small_nsg, ann_data):
    d, i = small_nsg.search(ann_data["queries"], 10)
    assert recall_at_k(i, ann_data["true_i"]) >= 0.95


# ---------------------------------------------------------------- pipeline
def test_tuned_pipeline_recall_and_memory(ann_data):
    idx = TunedGraphIndex(IndexParams(
        pca_dim=24, antihub_keep=0.9, ep_clusters=12, ef_search=48,
        graph_degree=12, build_knn_k=12, build_candidates=32,
    )).fit(ann_data["data"])
    d, i = idx.search(ann_data["queries"], 10)
    assert recall_at_k(i, ann_data["true_i"]) >= 0.85
    assert idx.ntotal == 1800  # alpha * N
    assert idx.base.shape[1] == 24
    # returned ids must be original-space ids
    assert int(np.asarray(i).max()) < 2000


def test_entry_points_reduce_hops(small_nsg, ann_data):
    """Paper Fig 3c: tuned entry points shorten search paths."""
    idx = small_nsg
    q = idx.project(ann_data["queries"])
    e1 = idx.eps.select(q)  # medoid (k=1)
    eps16 = fit_entry_points(jax.random.PRNGKey(0), idx.base, 16)
    e16 = eps16.select(q)
    _, _, h1 = beam_search(q, idx.base, idx.graph.neighbors, e1, ef=48, k=10)
    _, _, h16 = beam_search(q, idx.base, idx.graph.neighbors, e16, ef=48,
                            k=10)
    assert float(h16.mean()) <= float(h1.mean())
