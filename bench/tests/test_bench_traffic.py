"""The traffic generator: deterministic per seed, the right sizes, and the
same work for every seed."""
import sys
from collections import Counter
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, traffic  # noqa: E402

BULK = spec.traffic("sisap-bulk")
ONLINE = dict(BULK, arrival="poisson", rate_rps=200.0, set_size=1000,
              request_rows={"kind": "geometric", "p": 0.5, "max": 16},
              batch_window_s=0.002)


def test_bulk_splits_each_set_into_1024_row_requests():
    s = traffic.Schedule(BULK, seed=3, seconds=40)
    reqs = [s[i] for i in range(25)]
    assert [r.rows for r in reqs[:10]] == [1024] * 9 + [10000 - 9 * 1024]
    assert [r.row0 for r in reqs[:3]] == [0, 1024, 2048]
    assert [r.set_index for r in reqs[:21]] == [0] * 10 + [1] * 10 + [2]
    assert all(r.due_s == 0.0 for r in reqs)
    assert s.closed


def test_sets_cycle_through_the_pool():
    s = traffic.Schedule(BULK, seed=3, seconds=40)
    n = len(s.per_set) * BULK["query_sets"]
    assert s[n].set_index == 0 and s[n - 1].set_index == BULK["query_sets"] - 1


def test_query_sets_deterministic_per_seed():
    import jax.numpy as jnp
    mix = dict(BULK, set_size=50, query_sets=2)
    corpus = jnp.arange(200 * 8, dtype=jnp.float32).reshape(200, 8)
    a = traffic.query_sets(mix, corpus, 2**31 + 11)
    b = traffic.query_sets(mix, corpus, 2**31 + 11)
    c = traffic.query_sets(mix, corpus, 2**31 + 12)
    assert len(a) == 2 and a[0].shape == (50, 8)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], a[1])


def test_open_loop_same_sizes_and_gaps_in_another_order():
    a = traffic.Schedule(ONLINE, seed=1, seconds=10)
    b = traffic.Schedule(ONLINE, seed=2, seconds=10)
    assert Counter(n for _, n in a.per_set) == Counter(n for _, n in b.per_set)
    assert [n for _, n in a.per_set] != [n for _, n in b.per_set]
    assert sum(n for _, n in a.per_set) == ONLINE["set_size"]
    assert max(n for _, n in a.per_set) <= 16
    gaps = [np.sort(np.diff(s.due, prepend=0.0)) for s in (a, b)]
    assert np.allclose(gaps[0], gaps[1], rtol=0, atol=1e-9)
    assert a.due[-1] > 10
    assert all(np.diff(a.due) >= 0)
    c = traffic.Schedule(ONLINE, seed=1, seconds=10)
    assert [c[i] for i in range(50)] == [a[i] for i in range(50)]
