"""The plain reference and the compared numbers against numpy brute
force."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from bench import check, reference  # noqa: E402
from bench.traffic import Request  # noqa: E402


def _data(n=700, d=48, q=37, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, d)).astype(np.float32),
            rng.normal(size=(q, d)).astype(np.float32))


def _numpy_topk(q, x, k):
    d = ((q[:, None, :].astype(np.float64) - x[None].astype(np.float64))
         ** 2).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, ids, 1), ids


@pytest.mark.parametrize("row_block", [64, 700, 100000])
def test_reference_matches_numpy_brute_force(row_block):
    import jax.numpy as jnp
    x, q = _data()
    want_d, want_i = _numpy_topk(q, x, 10)
    got_d, got_i = reference.search(q, jnp.asarray(x), 10, query_block=16,
                                    row_block=row_block)
    assert np.array_equal(got_i, want_i)
    assert np.allclose(got_d, want_d, rtol=1e-4, atol=1e-4)


def test_exact_sqdist_is_float64():
    import jax.numpy as jnp
    x, q = _data()
    ids = np.array([[3, 5, -1]] * q.shape[0])
    got = reference.exact_sqdist(q, jnp.asarray(x), ids, block=8)
    want = ((q[:, None, :].astype(np.float64)
             - x[ids[:, :2]].astype(np.float64)) ** 2).sum(-1)
    assert np.array_equal(got[:, :2], want)
    assert np.isnan(got[:, 2]).all()


def test_bf16x3_rounds_more_than_highest():
    import jax.numpy as jnp
    x, q = _data(n=300, d=256)
    exact, _ = _numpy_topk(q, x, 10)
    hi, _ = reference.search(q, jnp.asarray(x), 10, query_block=64)
    lo, _ = reference.search(q, jnp.asarray(x), 10, query_block=64,
                             precision="bf16x3")
    err_hi = np.abs(hi - exact).max() / np.abs(exact).max()
    err_lo = np.abs(lo - exact).max() / np.abs(exact).max()
    assert err_lo > 3 * err_hi


def _answers(q, ids, dists):
    done = [(Request(0, 0, q.shape[0], 0.0), (dists, ids))]
    return check.gather(done, 10)


LIMITS = {"recall_at_10_min": 0.9, "dist_err_max": 1e-5,
          "distances": "exact"}


def test_recall_and_dist_err_of_exact_answers():
    import jax.numpy as jnp
    x, q = _data()
    d, i = _numpy_topk(q, x, 10)
    ans = _answers(q, i, d.astype(np.float32))
    checks, readings = check.compare(ans, [q], jnp.asarray(x), 10, LIMITS,
                                     sample=1000, seed=1)
    assert readings["recall_at_10"] == 1.0
    assert readings["sampled"] == q.shape[0]
    assert checks["dist_err_max"]["value"] < 1e-6
    assert check.holds(checks)


def test_recall_counts_hits_over_k():
    import jax.numpy as jnp
    x, q = _data()
    d, i = _numpy_topk(q, x, 12)
    # answer the 3rd..12th nearest: 8 of the true 10
    ans = _answers(q, i[:, 2:12], d[:, 2:12].astype(np.float32))
    checks, readings = check.compare(ans, [q], jnp.asarray(x), 10, LIMITS,
                                     sample=1000, seed=1)
    assert readings["recall_at_10"] == pytest.approx(0.8)
    assert not check.holds(checks)


def test_offset_distances_pass_and_wrong_distances_fail():
    import jax.numpy as jnp
    x, q = _data()
    d, i = _numpy_topk(q, x, 10)
    limits = dict(LIMITS, distances="offset")
    shifted = (d - 0.25).astype(np.float32)       # one offset per query
    checks, _ = check.compare(_answers(q, i, shifted), [q], jnp.asarray(x),
                              10, limits, sample=1000, seed=1)
    assert check.holds(checks)
    checks, _ = check.compare(_answers(q, i, shifted), [q], jnp.asarray(x),
                              10, LIMITS, sample=1000, seed=1)
    assert not check.holds(checks)
    wrong = d.astype(np.float32).copy()
    wrong[0, 3] *= 1.001
    checks, _ = check.compare(_answers(q, i, wrong), [q], jnp.asarray(x),
                              10, limits, sample=1000, seed=1)
    assert checks["dist_err_max"]["value"] > 1e-4


def test_malformed_answers_are_counted():
    x, q = _data()
    d, i = _numpy_topk(q, x, 10)
    i = i.copy()
    i[0, 1] = i[0, 0]                 # an id twice
    i[1, 0] = -1                      # not found
    i[2, 0] = x.shape[0]              # out of range
    dd = d.astype(np.float32).copy()
    dd[3, 0] = dd[3, 5] + 1           # not ascending
    assert check.malformed(_answers(q, i, dd), x.shape[0], 10) == 4


def test_failed_requests_count_their_rows():
    from repro.serve.resilience import SearchFailure
    x, q = _data()
    d, i = _numpy_topk(q, x, 10)
    done = [(Request(0, 0, 30, 0.0), (d[:30], i[:30])),
            (Request(0, 30, 7, 0.0), SearchFailure("boom", "X", 1))]
    ans = check.gather(done, 10)
    assert ans.failed == 7 and ans.ids.shape == (30, 10)
