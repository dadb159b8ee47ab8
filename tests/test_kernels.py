"""Per-kernel validation: shape/dtype sweeps + hypothesis properties, each
Pallas kernel (interpret=True) against its pure-jnp ref.py oracle.

Only the property tests need hypothesis; the sweeps and the traversal
parity tests run in every environment (the tier-1 container has no
hypothesis — gating the whole module on it once hid a broken kernel
import)."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                     # container: property tests skip
    HAVE_HYPOTHESIS = False

from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.gather_dist import gather_dist
from repro.kernels.l2topk import l2_topk

SETTINGS = dict(max_examples=15, deadline=None)
REPO = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------ import order
@pytest.mark.parametrize("package", [
    "beam_hop", "embedding_bag", "gather_dist", "l2topk", "lut_dist",
    "topk_merge"])
def test_kernel_package_imports_first(package):
    """Each kernel package imports in a fresh interpreter before anything
    else of the repo (no import cycle through ``core``)."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run(
        [sys.executable, "-c", f"import repro.kernels.{package}"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


# ------------------------------------------------------------------ l2topk
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("q,n,d,k,bq,bn", [
    (8, 64, 16, 5, 4, 32),
    (16, 257, 32, 10, 8, 64),     # n not divisible by block
    (3, 33, 128, 10, 8, 16),      # q < block_q
    (32, 1024, 96, 1, 32, 256),   # k=1
])
def test_l2topk_sweep(q, n, d, k, bq, bn, dtype):
    kq = jax.random.normal(jax.random.PRNGKey(0), (q, d)).astype(dtype)
    kx = jax.random.normal(jax.random.PRNGKey(1), (n, d)).astype(dtype)
    d1, i1 = l2_topk(kq, kx, k, backend="pallas", block_q=bq, block_n=bn)
    d2, i2 = l2_topk(kq, kx, k, backend="jnp")
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=tol,
                               atol=tol)
    assert (np.asarray(d1) >= 0).all()
    assert (np.diff(np.asarray(d1), axis=1) >= -tol).all()  # ascending


if HAVE_HYPOTHESIS:
    @settings(**SETTINGS)
    @given(q=st.integers(1, 12), n=st.integers(12, 200),
           d=st.integers(4, 48), k=st.integers(1, 10),
           seed=st.integers(0, 2**31 - 1))
    def test_l2topk_property(q, n, d, k, seed):
        kq = jax.random.normal(jax.random.PRNGKey(seed), (q, d))
        kx = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, d))
        d1, i1 = l2_topk(kq, kx, min(k, n), backend="pallas", block_q=8,
                         block_n=64)
        d2, _ = l2_topk(kq, kx, min(k, n), backend="jnp")
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-3, atol=1e-3)
        ii = np.asarray(i1)
        assert ((ii >= 0) & (ii < n)).all()
        # ids are distinct per row
        for row in ii:
            assert len(set(row.tolist())) == len(row)


# -------------------------------------------------------------- gather_dist
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,n,d,r", [(2, 50, 8, 4), (8, 128, 64, 16),
                                     (1, 10, 256, 32)])
def test_gather_dist_sweep(b, n, d, r, dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, d)).astype(dtype)
    db = jax.random.normal(jax.random.PRNGKey(1), (n, d)).astype(dtype)
    ids = jax.random.randint(jax.random.PRNGKey(2), (b, r), -1, n)
    a = gather_dist(q, db, ids, backend="pallas")
    ref = gather_dist(q, db, ids, backend="jnp")
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(a), np.asarray(ref), rtol=tol,
                               atol=tol)
    # padding ids yield +inf
    assert np.isinf(np.asarray(a)[np.asarray(ids) < 0]).all()


if HAVE_HYPOTHESIS:
    @settings(**SETTINGS)
    @given(b=st.integers(1, 8), n=st.integers(4, 64), d=st.integers(2, 32),
           r=st.integers(1, 12), seed=st.integers(0, 2**31 - 1))
    def test_gather_dist_property(b, n, d, r, seed):
        q = jax.random.normal(jax.random.PRNGKey(seed), (b, d))
        db = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, d))
        ids = jax.random.randint(jax.random.PRNGKey(seed + 2), (b, r), -1, n)
        a = np.asarray(gather_dist(q, db, ids, backend="pallas"))
        ref = np.asarray(gather_dist(q, db, ids, backend="jnp"))
        np.testing.assert_allclose(a[np.isfinite(ref)],
                                   ref[np.isfinite(ref)],
                                   rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------ embedding_bag
@pytest.mark.parametrize("combiner", ["sum", "mean"])
@pytest.mark.parametrize("v,d,b,l", [(50, 16, 6, 5), (128, 64, 16, 1),
                                     (11, 8, 3, 20)])
def test_embedding_bag_sweep(v, d, b, l, combiner):
    t = jax.random.normal(jax.random.PRNGKey(0), (v, d))
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, l), -1, v)
    w = jax.random.uniform(jax.random.PRNGKey(2), (b, l))
    a = embedding_bag(t, ids, w, combiner, backend="pallas")
    ref = embedding_bag(t, ids, w, combiner, backend="jnp")
    np.testing.assert_allclose(np.asarray(a), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def test_embedding_bag_all_padding_row():
    t = jax.random.normal(jax.random.PRNGKey(0), (10, 4))
    ids = jnp.full((2, 3), -1, jnp.int32)
    out = embedding_bag(t, ids, None, "sum", backend="pallas")
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


if HAVE_HYPOTHESIS:
    @settings(**SETTINGS)
    @given(v=st.integers(2, 64), d=st.integers(2, 32), b=st.integers(1, 8),
           l=st.integers(1, 10), seed=st.integers(0, 2**31 - 1),
           combiner=st.sampled_from(["sum", "mean"]))
    def test_embedding_bag_property(v, d, b, l, seed, combiner):
        t = jax.random.normal(jax.random.PRNGKey(seed), (v, d))
        ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (b, l), -1, v)
        a = embedding_bag(t, ids, None, combiner, backend="pallas")
        ref = embedding_bag(t, ids, None, combiner, backend="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


# ----------------------------------------------- integration with the core
def test_gather_dist_matches_beam_default_gather():
    """kernels/gather_dist (both backends) is a drop-in for the batched
    traversal's default expansion (``dot_gather_dist``)."""
    from repro.core.beam_search import dot_gather_dist
    q = jax.random.normal(jax.random.PRNGKey(0), (6, 24))
    db = jax.random.normal(jax.random.PRNGKey(1), (80, 24))
    ids = jax.random.randint(jax.random.PRNGKey(2), (6, 12), 0, 80)
    want = dot_gather_dist(q, db, ids)
    for backend in ("jnp", "pallas"):
        got = gather_dist(q, db, ids, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_beam_batched_pallas_expansion_matches_ref(small_nsg, ann_data):
    """Full traversal with the Pallas expansion kernel lands on the same
    neighbors as the jnp reference expansion."""
    from repro.core.beam_search import beam_search
    idx = small_nsg
    q = idx.project(ann_data["queries"][:16])
    e = idx.eps.select(q)
    kw = dict(ef=32, k=10, max_iters=96, mode="fori")
    dj, ij, _ = beam_search(q, idx.base, idx.graph.neighbors, e,
                            gather_backend="jnp", **kw)
    dp, ip, _ = beam_search(q, idx.base, idx.graph.neighbors, e,
                            gather_backend="pallas", **kw)
    np.testing.assert_array_equal(np.asarray(ij), np.asarray(ip))
    np.testing.assert_allclose(np.asarray(dj), np.asarray(dp), rtol=1e-4,
                               atol=1e-4)


def test_l2topk_pallas_inside_flat_search(ann_data):
    """The kernel is a drop-in for the brute-force scorer."""
    from repro.core.flat import recall_at_k
    d, i = l2_topk(ann_data["queries"], ann_data["data"], 10,
                   backend="pallas", block_q=16, block_n=256)
    assert recall_at_k(i, ann_data["true_i"]) == 1.0


# -------------------------------------------------------------- topk_merge
def _keyed_candidates(seed, b, m, n_ids):
    """Candidate (ids, dists) where duplicate ids carry bit-equal dists —
    exactly the invariant the real callers guarantee (a pair's distance is
    computed by the same arithmetic wherever it appears)."""
    id_dist = jax.random.uniform(jax.random.PRNGKey(seed), (b, n_ids)) + 0.01
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (b, m), -1,
                             n_ids).astype(jnp.int32)
    rows = jnp.arange(b)[:, None]
    ds = jnp.where(ids >= 0, id_dist[rows, jnp.maximum(ids, 0)], jnp.inf)
    return ids, ds


@pytest.mark.parametrize("b,kcur,m,k,br", [
    # interpreted-mode Pallas on CPU makes the big grids ~30s each: the
    # small case keeps fast-lane coverage, the rest ride the slow lane
    pytest.param(17, 8, 19, 8, 8, marks=pytest.mark.slow,
                 id="17-8-19-8-8"),      # odd sizes, non-pow2 width
    pytest.param(64, 12, 44, 12, 64, marks=pytest.mark.slow,
                 id="64-12-44-12-64"),   # block_rows == b
    (5, 4, 3, 6, 2),                     # fewer candidates than k
    pytest.param(33, 20, 64, 10, 16, marks=pytest.mark.slow,
                 id="33-20-64-10-16"),   # truncating k
])
def test_topk_merge_pallas_matches_ref(b, kcur, m, k, br):
    from repro.kernels.topk_merge import topk_merge
    from repro.kernels.topk_merge.ref import topk_merge_ref

    cur_i, cur_d = _keyed_candidates(7, b, kcur, 3 * max(kcur, m))
    # dedup the current rows like a real table (unique valid ids per row)
    ci = np.array(cur_i)
    for r in range(b):
        seen = set()
        for c in range(kcur):
            if ci[r, c] in seen:
                ci[r, c] = -1
            seen.add(int(ci[r, c]))
    cur_i = jnp.asarray(ci)
    cur_d = jnp.where(cur_i >= 0, cur_d, jnp.inf)
    cur_f = (jax.random.uniform(jax.random.PRNGKey(9), (b, kcur)) < 0.5) \
        & (cur_i >= 0)
    cand_i, cand_d = _keyed_candidates(7, b, m, 3 * max(kcur, m))

    ri, rd, rf = topk_merge_ref(cur_i, cur_d, cur_f, cand_i, cand_d, k)
    pi, pd, pf = topk_merge(cur_i, cur_d, cur_f, cand_i, cand_d, k,
                            backend="pallas", block_rows=br)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(pi))
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(pd))
    np.testing.assert_array_equal(np.asarray(rf), np.asarray(pf))


@pytest.mark.parametrize("b,m,k", [
    (23, 37, 9), (8, 8, 8),
    pytest.param(50, 130, 24, marks=pytest.mark.slow, id="50-130-24"),
])
def test_topk_pool_pallas_matches_ref(b, m, k):
    from repro.kernels.topk_merge import topk_pool
    from repro.kernels.topk_merge.ref import topk_pool_ref

    ids, ds = _keyed_candidates(11, b, m, 2 * m)
    ri, rd = topk_pool_ref(ids, ds, k)
    pi, pd = topk_pool(ids, ds, k, backend="pallas", block_rows=16)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(pi))
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(pd))


def test_topk_merge_backend_dispatch():
    from repro.kernels.topk_merge import resolve_merge_backend
    assert resolve_merge_backend("jnp") == "jnp"
    assert resolve_merge_backend("pallas") == "pallas"
    # None resolves by platform: jnp everywhere but TPU
    expected = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert resolve_merge_backend(None) == expected
    with pytest.raises(ValueError, match="merge backend"):
        resolve_merge_backend("bogus")


# ---------------------------------------------------------------- beam_hop
def _hop_inputs(seed, nq=10, n=300, d=16, r=8, ef=16):
    """Random mid-search hop state: pools with inf-padded empty lanes, some
    visited marks, and a few inactive (sel < 0) queries."""
    keys = [jax.random.PRNGKey(seed + i) for i in range(7)]
    db = jax.random.normal(keys[0], (n, d))
    nbrs = jax.random.randint(keys[1], (n, r), -1, n)
    pi = jax.random.randint(keys[2], (nq, ef), -1, n)
    pd = jnp.where(pi >= 0, jax.random.uniform(keys[3], (nq, ef)) * 20,
                   jnp.inf)
    pv = (pi < 0) | (jax.random.uniform(keys[4], (nq, ef)) < 0.3)
    sel = jnp.where(jnp.arange(nq) % 3 == 0, -1,
                    jax.random.randint(keys[5], (nq,), 0, n))
    q = jax.random.normal(keys[6], (nq, d))
    return sel, nbrs, pi, pd, pv, q, db


# which candidate slots are live: the kernel fetches a row for those only
_HOP_LIVENESS = {
    "third_lane_dead": lambda sel, nbrs: (sel, nbrs),
    "block_dead": lambda sel, nbrs: (sel.at[:8].set(-1), nbrs),
    "empty_row": lambda sel, nbrs: (sel, nbrs.at[sel[1]].set(-1)),
    "all_live": lambda sel, nbrs: (jnp.abs(sel), jnp.abs(nbrs)),
    # rows -1-padded past an out-degree of 0..R beside the dead lanes: a
    # step mixes dead lanes, empty rows and partly live ones
    "padded_rows": lambda sel, nbrs: (sel, jnp.where(
        jnp.arange(nbrs.shape[1])[None, :]
        < (jnp.arange(nbrs.shape[0]) % (nbrs.shape[1] + 1))[:, None],
        nbrs, -1)),
}

# quantized hops as (dist_backend, M, C): M off the 128-lane tile (4, 48,
# 300: PQ300's width), int8 at d'=600, and a C that is not 256
_HOP_DISTS = {"f32": ("f32", 0, 0), "pq": ("pq", 4, 16),
              "pq-m48": ("pq", 48, 256), "pq-m300": ("pq", 300, 256),
              "int8": ("int8", 600, 256)}


def _lut_codes(key, n, m, c):
    """(n, m) uint8 codes in 0..c-1 with both ends in every row."""
    codes = jax.random.randint(key, (n, m), 0, c)
    return codes.at[:, 0].set(0).at[:, -1].set(c - 1).astype(jnp.uint8)


@pytest.mark.parametrize("liveness", sorted(_HOP_LIVENESS))
@pytest.mark.parametrize("dist_backend", sorted(_HOP_DISTS))
def test_beam_hop_pallas_bitexact_vs_ref(dist_backend, liveness):
    """One fused hop: the Pallas kernel (interpret) reproduces the jnp ref
    bit-for-bit — ids, distances, visited marks AND work counters — for
    every pattern of dead lanes (sel < 0) and -1 neighbour slots, and for
    PQ and int8 tables of several widths. The kernel takes the ADC table
    centroid-major; the ref takes it (Q, M, C)."""
    from repro.kernels.beam_hop import beam_hop_pallas, beam_hop_ref
    from repro.kernels.row_gather import centroid_major

    sel, nbrs, pi, pd, pv, q, db = _hop_inputs(3)
    sel, nbrs = _HOP_LIVENESS[liveness](sel, nbrs)
    dist_backend, m, c = _HOP_DISTS[dist_backend]
    q_kernel = q
    if dist_backend != "f32":
        db = _lut_codes(jax.random.PRNGKey(11), db.shape[0], m, c)
        q = jax.random.uniform(jax.random.PRNGKey(12), (q.shape[0], m, c))
        q_kernel = centroid_major(q)
    ref = beam_hop_ref(sel, nbrs, pi, pd, pv, q, db,
                       dist_backend=dist_backend)
    out = beam_hop_pallas(sel, nbrs, pi, pd, pv, q_kernel, db,
                          dist_backend=dist_backend, interpret=True)
    for r_, o_ in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(r_), np.asarray(o_))


_HOP_CODECS = {}


def _hop_codec(idx, backend):
    """Per-(index, backend) codec cache: one k-means fit per dist backend."""
    key = (id(idx), backend)
    if key not in _HOP_CODECS:
        from repro.core.quant import make_codec
        # m=8 keeps the PQ k-means fit cheap; parity is m-agnostic
        codec = make_codec(backend, idx.base.shape[1], 8)
        codec.fit(idx.base, key=jax.random.PRNGKey(5))
        codes = getattr(codec, "codes", None)
        codes = codec.encode(idx.base) if codes is None else codes
        _HOP_CODECS[key] = (codec, codes)
    return _HOP_CODECS[key]


@pytest.mark.parametrize("mode", ["while", "fori"])
@pytest.mark.parametrize("dist_backend", ["f32", "pq", "int8"])
def test_fused_hop_bitexact_vs_staged(small_nsg, ann_data, dist_backend,
                                      mode):
    """Full traversal, fused vs staged, every dist backend x loop mode:
    ids, distances and all three work counters are bitwise identical.
    Both fused flavours run — 'jnp' (the ref) and 'pallas' (the kernel,
    interpret mode). The staged baseline uses gather_backend='jnp', whose
    diff-square arithmetic is the form the fused kernel computes (the
    default dot-formula gather is NOT bit-reproducible in-kernel)."""
    from repro.core.beam_search import beam_search

    idx = small_nsg
    q = idx.project(ann_data["queries"][:8])
    e = idx.eps.select(q)
    kw = dict(ef=16, k=8, max_iters=48, mode=mode, with_stats=True)
    if dist_backend != "f32":
        codec, codes = _hop_codec(idx, dist_backend)
        kw.update(dist_backend=dist_backend, codes=codes, lut=codec.lut(q))
    args = (q, idx.base, idx.graph.neighbors, e)
    ds, is_, ss = beam_search(*args, hop_backend="staged",
                              gather_backend="jnp", **kw)
    for flavour in ("jnp", "pallas"):
        df, if_, sf = beam_search(*args, hop_backend="fused",
                                  gather_backend=flavour, **kw)
        np.testing.assert_array_equal(np.asarray(is_), np.asarray(if_))
        np.testing.assert_array_equal(np.asarray(ds), np.asarray(df))
        for a, b in zip(ss, sf):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_hop_matches_vmap_layout_diffsq(small_nsg, ann_data):
    """The fused hop agrees with the staged hop scoring through a custom
    ``gather_dist``: a per-query diff-square, the arithmetic the kernel
    uses, lifted over the batch by ``jax.vmap``."""
    from repro.core.beam_search import beam_search

    def _diffsq(query, db, ids):
        rows = db[jnp.maximum(ids, 0)].astype(jnp.float32)
        d = jnp.sum((rows - query.astype(jnp.float32)) ** 2, -1)
        return jnp.where(ids >= 0, d, jnp.inf)

    idx = small_nsg
    q = idx.project(ann_data["queries"][:8])
    e = idx.eps.select(q)
    kw = dict(ef=16, k=8, max_iters=48, mode="fori")
    diffsq_b = jax.vmap(_diffsq, in_axes=(0, None, 0))
    dv, iv, _ = beam_search(q, idx.base, idx.graph.neighbors, e,
                            hop_backend="staged", gather_dist=diffsq_b, **kw)
    df, if_, _ = beam_search(q, idx.base, idx.graph.neighbors, e,
                             hop_backend="fused", gather_backend="jnp", **kw)
    np.testing.assert_array_equal(np.asarray(iv), np.asarray(if_))
    np.testing.assert_array_equal(np.asarray(dv), np.asarray(df))


def test_fused_rejects_custom_gather(small_nsg, ann_data):
    from repro.core.beam_search import beam_search
    idx = small_nsg
    q = idx.project(ann_data["queries"][:4])
    e = idx.eps.select(q)
    kw = dict(ef=16, k=8, max_iters=16, mode="fori")
    with pytest.raises(ValueError, match="custom gather_dist"):
        beam_search(q, idx.base, idx.graph.neighbors, e, hop_backend="fused",
                    gather_dist=lambda a, b, c: jnp.zeros(()), **kw)


if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), ef=st.sampled_from([12, 24, 40]))
    def test_fused_recall_equals_staged_property(small_nsg, ann_data, seed,
                                                 ef):
        """Recall@10 of the fused hop equals the staged hop's on fresh
        query draws at any beam width (bit-parity implies it; this checks
        the claim end-to-end through ground truth)."""
        from repro.core.beam_search import beam_search
        from repro.core.flat import FlatIndex, recall_at_k
        from repro.data import queries_like

        idx = small_nsg
        data = ann_data["data"]
        q = queries_like(jax.random.PRNGKey(seed), data, 8)
        _, ti = FlatIndex(data).search(q, 10)
        e = idx.eps.select(q)
        kw = dict(ef=max(ef, 10), k=10, max_iters=96, mode="while",
                  gather_backend="jnp")
        _, i_st, _ = beam_search(q, idx.base, idx.graph.neighbors, e,
                                 hop_backend="staged", **kw)
        _, i_fu, _ = beam_search(q, idx.base, idx.graph.neighbors, e,
                                 hop_backend="fused", **kw)
        assert recall_at_k(i_fu, ti) == recall_at_k(i_st, ti)


@pytest.mark.slow
def test_nn_descent_merge_backends_agree(ann_data):
    """The whole NN-Descent build is bit-identical across merge backends
    (same seed, same rounds — only the sort implementation differs)."""
    from repro.core.build import nn_descent
    data = ann_data["data"][:400]
    d1, i1 = nn_descent(data, 8, key=jax.random.PRNGKey(3), rounds=4,
                        merge_backend="jnp")
    d2, i2 = nn_descent(data, 8, key=jax.random.PRNGKey(3), rounds=4,
                        merge_backend="pallas")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2))
