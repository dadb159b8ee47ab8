"""Test-side overrides that run a cell of the benchmark at a tiny size on
the CPU: fewer corpus rows (the width stays 768), a smaller graph build,
short query sets, and a compile cache in a temporary directory. The
benchmark's own files are read as they are and changed only in memory."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

N_ROWS = 2000
BATCH = 64


def shrink(monkeypatch, cache: Path, hop_backend: str = "fused") -> None:
    """Patch the harness to run on the CPU at ``N_ROWS`` rows."""
    import jax

    from bench import run, spec

    orig_config, orig_traffic = spec.config, spec.traffic

    def config(bench, name, root=spec.ROOT):
        cfg, _ = orig_config(bench, name, root)
        cfg = copy.deepcopy(cfg)
        cfg["corpus"]["n"] = N_ROWS
        ann = cfg["index"]["ann_config"]
        ann["n_database"] = N_ROWS
        if cfg["index"]["kind"] == "pipeline":
            ann.update(ep_clusters=8, graph_degree=16, build_knn_k=16,
                       build_candidates=32, knn_backend="exact")
            # the fused hop (interpret mode here) keeps the chip's
            # distance arithmetic
            cfg["search"]["hop_backend"] = hop_backend
        cfg["max_batch"] = BATCH
        return cfg, json.dumps(cfg).encode()

    def traffic(name):
        mix = dict(orig_traffic(name))
        mix.update(set_size=150, query_sets=2, check_sample=400,
                   request_rows={"kind": "fixed", "rows": BATCH})
        return mix

    monkeypatch.setattr(spec, "config", config)
    monkeypatch.setattr(spec, "traffic", traffic)
    monkeypatch.setattr(run, "require_devices", lambda chips: jax.devices())
    monkeypatch.setattr(run, "CACHE", cache)
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
