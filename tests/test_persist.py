"""Index persistence: save/load round trips, integrity validation, and
crash consistency.

The contract under test (core/persist.py + core/validate.py + the payload
layer in checkpoint/checkpointer.py):

  * every registered factory example round-trips through
    ``save_index`` / ``load_index`` with BIT-IDENTICAL search results —
    no refit, no re-clustering, no re-encoding on load;
  * a loaded index passes ``validate_index`` (structural invariants), and
    violated invariants raise ``IndexIntegrityError`` naming the invariant;
  * corrupted or torn snapshots fail closed: checksum mismatch on load,
    and the stepped layout falls back to the newest VALID step.
"""
import os
import shutil
import warnings

import jax
import numpy as np
import pytest

from repro.core import build_index, load_index, save_index, validate_index
from repro.core.index_api import available_factories
from repro.core.persist import index_from_state, index_state
from repro.core.validate import IndexIntegrityError
from repro.data import clustered_vectors, queries_like

ALL_EXAMPLES = sorted({s for specs in available_factories().values()
                       for s in specs})
# PCA composition is a wrapper, not a registered component — cover it
# explicitly on a cheap inner family plus one graph spec.
PCA_SPECS = ["PCA24,Flat", "PCA24,IVF16", "PCA24,NSG12,EP8"]


@pytest.fixture(scope="module")
def persist_data():
    key = jax.random.PRNGKey(7)
    data = clustered_vectors(key, 900, 32, n_clusters=8)
    queries = queries_like(jax.random.PRNGKey(8), data, 24)
    return data, queries


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_state():
    """This module compiles ~20 throwaway index builds/searches; release
    the executables afterwards so the tail of a full-suite run doesn't
    carry the extra jit-cache footprint (XLA compiles late in a long
    single-process run are sensitive to it)."""
    yield
    jax.clear_caches()


def _roundtrip_identical(idx, data, queries, tmp_path, label):
    snap = os.path.join(str(tmp_path), "snap")
    save_index(idx, snap)
    loaded = load_index(snap)          # verifies checksums + invariants
    assert type(loaded) is type(idx), label
    assert loaded.ntotal == idx.ntotal and loaded.dim == idx.dim
    d0, i0 = idx.search(queries, 10)
    d1, i1 = loaded.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1),
                                  err_msg=f"{label}: ids differ after load")
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1),
                                  err_msg=f"{label}: dists differ after load")
    return loaded


@pytest.mark.parametrize("spec", ALL_EXAMPLES)
def test_roundtrip_every_registered_example(spec, persist_data, tmp_path):
    """Bit-identical save -> load -> search for every registered factory
    example (the registry IS the coverage list: a new family registered
    with examples lands here automatically). The quantized specs
    (,PQ8x8 / ,SQ8) cover the pq / int8 dist backends."""
    data, queries = persist_data
    idx = build_index(spec, data, key=jax.random.PRNGKey(0))
    loaded = _roundtrip_identical(idx, data, queries, tmp_path, spec)
    assert getattr(loaded, "spec", None) == getattr(idx, "spec", None)


@pytest.mark.parametrize("spec", PCA_SPECS)
def test_roundtrip_pca_composition(spec, persist_data, tmp_path):
    data, queries = persist_data
    idx = build_index(spec, data, key=jax.random.PRNGKey(0))
    _roundtrip_identical(idx, data, queries, tmp_path, spec)


def test_roundtrip_sharded(persist_data, tmp_path):
    from repro.core.distributed import ShardedFactoryIndex
    data, queries = persist_data
    idx = ShardedFactoryIndex("NSG12,EP4", n_shards=3)
    idx.fit(data, key=jax.random.PRNGKey(0))
    loaded = _roundtrip_identical(idx, data, queries, tmp_path, "sharded")
    assert loaded.n_shards == 3
    assert [type(s).__name__ for s in loaded.subs] \
        == [type(s).__name__ for s in idx.subs]


def test_state_dict_is_host_serializable(persist_data):
    """index_state carries JSON-safe meta + plain arrays (what the payload
    writer needs); reconstructing from it alone matches the original."""
    import json

    data, queries = persist_data
    idx = build_index("IVF16,PQ8", data)
    state = index_state(idx)
    json.dumps(state["meta"])                    # meta must be JSON-safe
    clone = index_from_state({
        "family": state["family"],
        "meta": state["meta"],
        "arrays": {k: np.asarray(v) for k, v in state["arrays"].items()},
    })
    _, i0 = idx.search(queries, 10)
    _, i1 = clone.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_restore_manifest_with_removed_compaction_knob(persist_data):
    """Snapshots written while ``compact_every`` (active-query compaction)
    still existed carry it in two manifests: the graph's ``IndexParams``
    and the sharded wrapper's overrides. A stored 0 (off) restores to the
    same search; a non-zero one is refused by name."""
    import copy

    from repro.core.distributed import ShardedFactoryIndex
    data, queries = persist_data
    idx = ShardedFactoryIndex("NSG12,EP4", n_shards=2)
    idx.fit(data, key=jax.random.PRNGKey(0))
    state = index_state(idx)
    old = copy.deepcopy(state)
    old["meta"]["overrides"]["compact_every"] = 0
    for sub in old["meta"]["subs"]:
        sub["meta"]["params"]["compact_every"] = 0
    d0, i0 = idx.search(queries, 10)
    d1, i1 = index_from_state(old).search(queries, 10)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    for where in ("overrides", "params"):
        bad = copy.deepcopy(old)
        if where == "overrides":
            bad["meta"]["overrides"]["compact_every"] = 8
        else:
            bad["meta"]["subs"][0]["meta"]["params"]["compact_every"] = 8
        with pytest.raises(ValueError, match="compact_every"):
            index_from_state(bad)


# ---------------------------------------------------------------------------
# validate_index: loaded indexes pass; corrupted structures name the
# violated invariant
# ---------------------------------------------------------------------------


def test_validate_passes_on_fresh_builds(persist_data):
    data, _ = persist_data
    for spec in ("Flat", "IVF16", "PQ8", "HNSW8", "NSG12,EP8"):
        validate_index(build_index(spec, data))


def test_validate_catches_out_of_range_neighbor(persist_data):
    data, _ = persist_data
    idx = build_index("NSG12,EP8", data)
    bad = np.asarray(idx.graph.neighbors).copy()
    bad[0, 0] = idx.ntotal + 5                    # points past the table
    idx.graph = idx.graph._replace(
        neighbors=jax.numpy.asarray(bad))
    with pytest.raises(IndexIntegrityError) as ei:
        validate_index(idx)
    assert ei.value.invariant == "neighbor_range"


def test_validate_catches_nonfinite_vectors(persist_data):
    data, _ = persist_data
    idx = build_index("Flat", data)
    poisoned = np.asarray(idx.data).copy()
    poisoned[3, 1] = np.nan
    idx.data = jax.numpy.asarray(poisoned)
    with pytest.raises(IndexIntegrityError) as ei:
        validate_index(idx)
    assert ei.value.invariant == "finite"


def test_validate_catches_pq_code_overflow(persist_data):
    from repro.core.pq import PQIndex
    data, _ = persist_data
    # 16 centroids (not the uint8-saturating 256) so an out-of-range code
    # value is representable in the code dtype at all
    idx = PQIndex(m=8, n_centroids=16).fit(data)
    codes = np.asarray(idx.codec.codes).copy()
    codes[0, 0] = idx.codec.n_centroids          # == ksub -> out of range
    idx.codec.codes = jax.numpy.asarray(codes)
    with pytest.raises(IndexIntegrityError) as ei:
        validate_index(idx)
    assert ei.value.invariant == "pq_codes"


# ---------------------------------------------------------------------------
# crash consistency: torn writes, bit rot, stepped fallback
# ---------------------------------------------------------------------------


def test_load_rejects_corrupted_snapshot(persist_data, tmp_path):
    from repro.serve.faults import corrupt_payload
    data, _ = persist_data
    snap = os.path.join(str(tmp_path), "snap")
    save_index(build_index("IVF16", data), snap)
    corrupt_payload(snap, seed=3)
    with pytest.raises(IndexIntegrityError) as ei:
        load_index(snap)
    assert ei.value.invariant == "checksum"


def test_stepped_load_falls_back_past_corruption(persist_data, tmp_path):
    """The crash scenario end to end: a partial ``step_N.tmp`` (writer
    died before the atomic rename) plus a corrupted newest committed step
    must not stop ``load_index`` — it skips both via checksum/commit
    semantics and restores the newest VALID step."""
    from repro.serve.faults import corrupt_payload
    data, queries = persist_data
    root = os.path.join(str(tmp_path), "steps")
    idx = build_index("NSG12,EP8", data)
    save_index(idx, root, step=1)
    save_index(idx, root, step=2)

    # torn write: a .tmp dir that never got renamed (no manifest commit)
    tmp_dir = os.path.join(root, "step_00000003.tmp")
    os.makedirs(tmp_dir)
    with open(os.path.join(tmp_dir, "arrays.npz"), "wb") as f:
        f.write(b"partial garbage")

    # bit rot on the newest COMMITTED step
    corrupt_payload(os.path.join(root, "step_00000002"), seed=11)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        loaded = load_index(root)
    _, i0 = idx.search(queries, 10)
    _, i1 = loaded.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_stepped_load_all_corrupt_raises(persist_data, tmp_path):
    from repro.serve.faults import corrupt_payload
    data, _ = persist_data
    root = os.path.join(str(tmp_path), "steps")
    save_index(build_index("Flat", data), root, step=1)
    corrupt_payload(os.path.join(root, "step_00000001"), seed=5)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with pytest.raises(IndexIntegrityError):
            load_index(root)


def test_load_missing_path_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_index(os.path.join(str(tmp_path), "nope"))


# ---------------------------------------------------------------------------
# Checkpointer satellites: lifecycle + stray-entry hygiene
# ---------------------------------------------------------------------------


def test_checkpointer_save_after_close_raises(tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer
    ck = Checkpointer(str(tmp_path))
    ck.save(0, {"w": np.zeros(3)}, block=True)
    ck.close()
    with pytest.raises(RuntimeError, match="closed"):
        ck.save(1, {"w": np.ones(3)})
    ck.close()                                   # idempotent


def test_checkpointer_all_steps_ignores_strays(tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer
    ck = Checkpointer(str(tmp_path))
    ck.save(3, {"w": np.zeros(2)}, block=True)
    os.makedirs(os.path.join(str(tmp_path), "step_final"))
    os.makedirs(os.path.join(str(tmp_path), "notes"))
    with open(os.path.join(str(tmp_path), "step_07.bak"), "w") as f:
        f.write("x")
    assert ck.all_steps() == [3]                 # no ValueError, no strays
    ck.close()


def test_checkpointer_gcs_orphan_tmps_on_init(tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer
    orphan = os.path.join(str(tmp_path), "step_00000009.tmp")
    os.makedirs(orphan)
    ck = Checkpointer(str(tmp_path))
    assert not os.path.exists(orphan)
    ck.close()


def test_checkpointer_error_cleared_after_wait(tmp_path, monkeypatch):
    """A failed background save surfaces in exactly one wait(); later
    save/wait cycles are not poisoned by the stale error."""
    import repro.checkpoint.checkpointer as cc
    ck = cc.Checkpointer(str(tmp_path))
    real = cc.write_payload

    def boom(*a, **kw):
        raise OSError("disk on fire")

    monkeypatch.setattr(cc, "write_payload", boom)
    ck.save(0, {"w": np.zeros(2)})
    with pytest.raises(OSError, match="disk on fire"):
        ck.wait()
    monkeypatch.setattr(cc, "write_payload", real)
    ck.save(1, {"w": np.zeros(2)})
    ck.wait()                                    # must NOT re-raise
    assert ck.all_steps() == [1]
    ck.close()
