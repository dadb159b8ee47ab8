"""The system under test, as a configuration names it.

``index.kind`` is "pipeline" (the paper's tuned graph, built by
``launch/serve.build_ann_index`` from an ``ANNConfig``) or "spec" (a factory
spec such as "Flat", built by the same entry). A pipeline is built once per
checkout and kept as a snapshot (``core/persist.save_index``); later runs
restore it with ``load_index``, which verifies every checksum and runs
``validate_index``. The snapshot's key hashes the configuration file, the
corpus and every source file of the program, so a changed program never
serves an index its parent built.
"""
from __future__ import annotations

import hashlib
import json
import time
from pathlib import Path
from typing import Callable, Tuple


def program_hash(src: Path) -> str:
    h = hashlib.sha256()
    for p in sorted((src / "repro").rglob("*.py")):
        h.update(str(p.relative_to(src)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def snapshot_key(cfg: dict, cfg_bytes: bytes, src: Path) -> str:
    h = hashlib.sha256(cfg_bytes)
    h.update(json.dumps(cfg["corpus"], sort_keys=True).encode())
    h.update(program_hash(src).encode())
    return f"{cfg['name']}-{h.hexdigest()[:16]}"


def open_index(cfg: dict, cfg_bytes: bytes, corpus, src: Path,
               cache: Path, log: Callable[[str], None]) -> Tuple[object, dict]:
    """(index, info): build, or restore the snapshot this build left."""
    import jax
    from repro.configs.base import ANNConfig
    from repro.core import load_index, save_index
    from repro.launch.serve import build_ann_index

    spec = cfg["index"]
    ann = ANNConfig(**spec["ann_config"])
    if (ann.n_database, ann.dim) != tuple(corpus.shape):
        raise ValueError(f"ann_config N x dim {ann.n_database} x {ann.dim} "
                         f"is not the corpus's {tuple(corpus.shape)}")
    key = jax.random.PRNGKey(spec["build_seed"])
    info = {"kind": spec["kind"]}
    t = time.perf_counter()
    if spec["kind"] == "spec":
        idx = build_ann_index(ann, corpus, key, spec=spec["spec"])
        info["built_s"] = time.perf_counter() - t
        return idx, info
    if spec["kind"] != "pipeline":
        raise ValueError(f"unknown index kind {spec['kind']!r}")
    path = cache / "index" / snapshot_key(cfg, cfg_bytes, src)
    if (path / "manifest.json").exists():
        idx = load_index(str(path))
        info["restored_s"] = time.perf_counter() - t
        log(f"restored snapshot {path.name} in {info['restored_s']:.3f}s "
            f"(checksums verified, invariants validated); built nothing")
        return idx, info
    idx = build_ann_index(ann, corpus, key)
    info["built_s"] = time.perf_counter() - t
    st = idx.build_stats
    log(f"built {cfg['name']} in {info['built_s']:.3f}s; stage seconds: "
        + ", ".join(f"{k}={v:.3f}" for k, v in idx.stage_seconds.items()))
    log("nsg build_stats: " + ", ".join(
        f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in st._asdict().items()))
    t = time.perf_counter()
    save_index(idx, str(path))
    info["saved_s"] = time.perf_counter() - t
    log(f"saved snapshot {path.name} in {info['saved_s']:.3f}s")
    return idx, info
