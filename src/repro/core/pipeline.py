"""The paper's end-to-end pipeline (Fig. 2): AntiHub subsample -> PCA ->
NSG build -> k-means entry points; search = project -> select EP -> beam.

``IndexParams`` carries exactly the knobs the black-box tuner drives:
D (pca_dim), alpha (antihub_keep), k (ep_clusters) + ef_search.
"""
from __future__ import annotations

import copy
import functools
import logging
import threading
import time
from dataclasses import asdict, dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ANNConfig
from repro.core import antihub as antihub_mod
from repro.core.beam_search import beam_search
from repro.core.build import build_knn, reprune_nsg, resolve_backend
from repro.core.build.nn_descent import nn_descent
from repro.core.entry_points import EntryPointSelector, fit_entry_points
from repro.core.nsg import NSGGraph, build_nsg
from repro.core.pca import PCA, fit_pca
from repro.core.quant import make_codec
from repro.kernels.gather_dist import gather_dist as _gather_dist
from repro.serve.spans import span

log = logging.getLogger(__name__)

# Module-level structural-build counter: every TunedGraphIndex.fit (a real
# graph build: pools + prune + interconnect) increments it. Rebuild-free
# derivations (reprune, with_graph, the tuner's grid lookups, sharded
# reprune) do NOT — tests assert sweeps leave it untouched.
_N_STRUCTURAL_BUILDS = 0
_BUILDS_LOCK = threading.Lock()     # sharded fits build shards in threads

# NN-Descent refinement rounds for the antihub-subset reuse path: the
# filtered full-data table is already a good approximation, so a couple of
# patch rounds replace a from-scratch build (init passes + ~10 rounds).
SUBSET_PATCH_ROUNDS = 3


def structural_build_count() -> int:
    """Process-wide count of real (non-derived) NSG pipeline builds."""
    return _N_STRUCTURAL_BUILDS


@dataclass(frozen=True)
class IndexParams:
    pca_dim: int                  # D   (== input dim -> PCA disabled)
    antihub_keep: float = 1.0     # alpha (1.0 -> subsampling disabled)
    ep_clusters: int = 1          # k    (1 -> medoid, vanilla NSG)
    ef_search: int = 64
    graph_degree: int = 32
    build_knn_k: int = 32
    build_candidates: int = 64
    # α-RNG pruning slack (Zhang et al. "Prune, Don't Rebuild") applied to
    # squared distances; 1.0 is NSG's MRNG rule. NOT the paper's AntiHub
    # alpha (that is antihub_keep above). Larger values prune harder.
    alpha: float = 1.0
    # kNN-graph build backend: "exact" | "nndescent" | "auto" (see
    # core/build). Auto switches to NN-Descent at large N.
    knn_backend: str = "auto"
    # NSG candidate-pool backend (core/nsg): "search" beam-searches the
    # kNN graph toward every node (the classic recipe), "nndescent"
    # derives pools from the kNN table (forward ∪ reverse ∪ 1-hop — no
    # beam searches). "auto" = table-derived pools unless knn_backend is
    # explicitly "exact" (the table's distances are in hand either way;
    # only an explicit exact request keeps the classic beam pools).
    pools_backend: str = "auto"
    # NSG finishing pass (core/build/finish): "device" runs the reverse
    # interconnect + connectivity repair as fixed-shape jitted ops (what
    # "auto" resolves to), "host" keeps the original numpy path as the
    # parity baseline. Also selects the repair path under reprune().
    finish_backend: str = "auto"
    # Quantized-traversal serving (core/quant): "f32" traverses the
    # full-precision vectors; "pq" | "int8" traverses uint8 codes via
    # kernels/lut_dist and exact-reranks the top ``rerank`` beam survivors.
    # pq_m=0 auto-picks the largest divisor of the post-PCA dim <= dim/2.
    # rerank=0 skips the exact tail (pure ADC distances come back).
    dist_backend: str = "f32"
    pq_m: int = 0
    rerank: int = 64
    # Beam-hop serving backend (core/beam_search): "staged" runs the hop
    # as separate gather / distance / merge ops (the parity baseline),
    # "fused" runs kernels/beam_hop (one Pallas launch per hop — the
    # (Q, R) candidate block never touches HBM). "auto" = fused on TPU.
    hop_backend: str = "auto"
    # Straggler control (core/beam_search adaptive termination).
    # patience=0 keeps the stock full-pool-convergence rule bit-for-bit;
    # patience=p also stops a lane after p consecutive hops without a
    # top-k prefix improvement > eps.
    patience: int = 0
    eps: float = 0.0

    @staticmethod
    def from_config(cfg: ANNConfig) -> "IndexParams":
        return IndexParams(
            pca_dim=cfg.pca_dim, antihub_keep=cfg.antihub_keep,
            ep_clusters=cfg.ep_clusters, ef_search=cfg.ef_search,
            graph_degree=cfg.graph_degree, build_knn_k=cfg.build_knn_k,
            build_candidates=cfg.build_candidates,
            alpha=getattr(cfg, "prune_alpha", 1.0),
            knn_backend=getattr(cfg, "knn_backend", "auto"),
            pools_backend=getattr(cfg, "pools_backend", "auto"),
            finish_backend=getattr(cfg, "finish_backend", "auto"),
            dist_backend=getattr(cfg, "dist_backend", "f32"),
            pq_m=getattr(cfg, "pq_m", 0),
            rerank=getattr(cfg, "rerank", 64),
            hop_backend=getattr(cfg, "hop_backend", "auto"),
            patience=getattr(cfg, "patience", 0),
            eps=getattr(cfg, "eps", 0.0))


def drop_removed_knobs(fields: dict) -> dict:
    """Snapshot knob fields minus the knobs this version no longer has.

    Active-query compaction is gone: its stored slice length restores as
    before when 0 (off) or None; any other value could not be honoured.
    """
    fields = dict(fields)
    stored = fields.pop("compact_every", None)
    if stored:
        raise ValueError(
            f"snapshot sets compact_every={stored} (active-query "
            f"compaction), a knob that was removed; rebuild the index")
    return fields


class TunedGraphIndex:
    """antihub ∘ pca ∘ nsg ∘ entry-points, searchable. Fit is build-time."""

    def __init__(self, params: IndexParams):
        self.params = params
        self.kept_idx: Optional[jax.Array] = None    # internal -> original id
        self.pca: Optional[PCA] = None
        self.base: Optional[jax.Array] = None        # projected kept vectors
        self.graph: Optional[NSGGraph] = None
        self.eps: Optional[EntryPointSelector] = None
        self.build_seconds: float = 0.0
        self.knn_seconds: float = 0.0                # kNN-graph phase
        self.stage_seconds: dict = {}                # fit wall-clock/stage
        self.build_stats = None                      # NSGBuildStats of fit
        self.input_dim: int = 0
        self.knn_ids: Optional[jax.Array] = None     # build-time kNN table
        self.codec = None                            # core.quant codec
        self.codes: Optional[jax.Array] = None       # (N, M) uint8 db codes
        self.codec_backend: Optional[str] = None     # "pq" | "int8"
        self.last_search_stats = None                # BeamStats of last search

    # -- build ------------------------------------------------------------
    def fit(self, data: jax.Array, key: Optional[jax.Array] = None, *,
            antihub_knn_ids: Optional[jax.Array] = None):
        """Build the full pipeline.

        ``antihub_knn_ids``: precomputed (N, >=10) kNN ids of the *raw*
        database, reused for the AntiHub k-occurrence pass (the tuner
        computes them once and threads them through every trial instead of
        paying an O(N^2) pass per structural build).

        ``stage_seconds`` records each stage's wall-clock, timed to ready:
        antihub, pca, knn, nsg (split further in ``build_stats``),
        entry_points and, for a quantized index, quantize.
        """
        global _N_STRUCTURAL_BUILDS
        t0 = time.perf_counter()
        key = key if key is not None else jax.random.PRNGKey(0)
        p = self.params
        n, d0 = data.shape
        self.input_dim = d0

        stages = self.stage_seconds = {}
        t_stage = time.perf_counter()

        def lap(name, *ready):
            nonlocal t_stage
            jax.block_until_ready(ready)
            now = time.perf_counter()
            stages[name] = now - t_stage
            t_stage = now
            log.info("fit: %s %.1fs", name, stages[name])

        ah_ids = antihub_knn_ids
        if p.antihub_keep < 1.0:
            if ah_ids is None:
                _, ah_ids = build_knn(data, 10, backend=p.knn_backend,
                                      key=jax.random.fold_in(key, 17))
            self.kept_idx = antihub_mod.antihub_keep_indices(
                data, p.antihub_keep, k=10, knn_ids=ah_ids)
            sub = data[self.kept_idx]
        else:
            self.kept_idx = jnp.arange(n, dtype=jnp.int32)
            sub = data
        lap("antihub", sub)

        if p.pca_dim < d0:
            self.pca = fit_pca(sub, p.pca_dim)
            base = self.pca.transform(sub)
        else:
            self.pca = None
            base = sub
        self.base = base
        lap("pca", base)

        t_knn = time.perf_counter()
        resolved_knn = resolve_backend(p.knn_backend, base.shape[0])
        if (resolved_knn == "nndescent" and ah_ids is not None
                and p.antihub_keep < 1.0):
            # antihub reuse: the raw database's kNN table already exists
            # (the k-occurrence pass built it) — filter it to the kept
            # subset, remap ids, and let a few NN-Descent patch rounds
            # repair the filtering (dropped neighbors) and the projection
            # (distances re-evaluated in base space) instead of paying a
            # from-scratch subset build.
            remap = jnp.full((n,), -1, jnp.int32
                             ).at[self.kept_idx].set(
                jnp.arange(self.kept_idx.shape[0], dtype=jnp.int32))
            kept_tab = ah_ids[self.kept_idx]
            init = jnp.where(kept_tab >= 0,
                             remap[jnp.maximum(kept_tab, 0)], -1)
            knn_dists, knn_ids = nn_descent(
                base, p.build_knn_k, key=jax.random.fold_in(key, 23),
                init_ids=init, init_passes=1,
                rounds=SUBSET_PATCH_ROUNDS)
        else:
            knn_dists, knn_ids = build_knn(
                base, p.build_knn_k, backend=p.knn_backend,
                key=jax.random.fold_in(key, 23))
        self.knn_ids = knn_ids
        lap("knn", knn_ids)
        self.knn_seconds = time.perf_counter() - t_knn

        pools = p.pools_backend
        if pools == "auto":
            # table-derived pools whenever the kNN side is (or may be)
            # NN-Descent; explicit exact keeps the classic beam pools
            pools = "search" if p.knn_backend == "exact" else "nndescent"
        # stats are retained unconditionally: the sharded build path and
        # launch/tune --bench-build-out aggregate per-shard stage timings
        # from them after the fact
        self.graph, self.build_stats = build_nsg(
            base, knn_ids, degree=p.graph_degree,
            n_candidates=p.build_candidates,
            alpha=p.alpha, pools_backend=pools, knn_dists=knn_dists,
            finish_backend=p.finish_backend, with_stats=True)
        lap("nsg", self.graph.neighbors)
        self.eps = fit_entry_points(key, base, p.ep_clusters)
        lap("entry_points", self.eps.centroids)
        if p.dist_backend != "f32":
            self.quantize(key=jax.random.fold_in(key, 29))
            lap("quantize", self.codes)
        self.build_seconds = time.perf_counter() - t0
        with _BUILDS_LOCK:
            _N_STRUCTURAL_BUILDS += 1
        return self

    def quantize(self, dist_backend: Optional[str] = None,
                 pq_m: Optional[int] = None, *,
                 key: Optional[jax.Array] = None) -> "TunedGraphIndex":
        """Train a traversal codec on the projected base and encode it ONCE.

        Codes live beside the graph; ``with_graph``/``reprune`` derivations
        share them (a reprune changes edges, not vectors), so quantization
        is per *structural build* — tuner sweeps over alpha/degree/rerank
        never re-encode. Called automatically by ``fit`` when
        ``params.dist_backend != "f32"``; call explicitly to quantize an
        f32-built index after the fact.
        """
        assert self.base is not None, "fit() first"
        p = self.params
        backend = dist_backend or (
            p.dist_backend if p.dist_backend != "f32" else "pq")
        m = pq_m if pq_m is not None else p.pq_m
        key = key if key is not None else jax.random.PRNGKey(0)
        self.codec = make_codec(backend, self.base.shape[1], m)
        self.codec.fit(self.base, key=key)
        stored = getattr(self.codec, "codes", None)   # PQ keeps train codes
        self.codes = stored if stored is not None \
            else self.codec.encode(self.base)
        self.codec_backend = backend
        return self

    # -- rebuild-free derivation ("prune, don't rebuild") ------------------
    def with_graph(self, graph: NSGGraph,
                   eps: Optional[EntryPointSelector] = None):
        """Shallow clone serving a different (derived) graph.

        Shares base vectors / PCA / kept ids with ``self`` — the reprune
        serving path, so one structural build can back many
        (alpha, degree) trials.
        """
        out = copy.copy(self)
        out.graph = graph
        if eps is not None:
            out.eps = eps
        return out

    def reprune(self, *, alpha: float = 1.0,
                degree: Optional[int] = None) -> "TunedGraphIndex":
        """Derive a lower-degree / larger-alpha index with NO rebuild.

        O(N * R) gather-distances + one vmapped occlusion pass +
        connectivity repair — the §5.3 rebuild cost collapses to this.
        """
        assert self.graph is not None, "fit() first"
        g = reprune_nsg(self.base, self.graph, alpha=alpha, degree=degree,
                        knn_ids=self.knn_ids,
                        finish_backend=self.params.finish_backend)
        out = self.with_graph(g)
        out.params = replace(self.params, alpha=alpha,
                             graph_degree=g.neighbors.shape[1])
        return out

    # -- search -----------------------------------------------------------
    def project(self, queries: jax.Array) -> jax.Array:
        return self.pca.transform(queries) if self.pca is not None else queries

    def search(self, queries: jax.Array, k: int, params=None, *,
               ef: Optional[int] = None, mode: Optional[str] = None,
               rerank: Optional[int] = None,
               dist_backend: Optional[str] = None,
               hop_backend: Optional[str] = None,
               patience: Optional[int] = None,
               eps: Optional[float] = None):
        """Returns (dists (Q,k) in projected space, original ids (Q,k)).

        ``params`` is a ``core.index_api.SearchParams``; explicit keywords
        win over it, both fall back to fit-time defaults. Under
        ``dist_backend="pq"|"int8"`` the beam traverses the codec's uint8
        codes (one ``kernels/lut_dist`` call per hop) and the top
        ``rerank`` survivors are exactly rescored in f32 — the returned
        distances are exact for reranked entries, ADC approximations when
        ``rerank=0``. ``hop_backend`` ("staged" | "fused" | "auto") picks
        the per-hop execution (see ``IndexParams.hop_backend``).
        ``patience``/``eps`` enable adaptive early termination (0 = stock
        convergence, bit-for-bit).
        Per-hop work counters of the latest call are kept on the index —
        read them via ``search_stats()``.
        """
        assert self.graph is not None, "fit() first"
        if params is not None:
            ef = ef if ef is not None else params.ef_search
            mode = mode if mode is not None else params.mode
            if rerank is None:
                rerank = getattr(params, "rerank", None)
            if dist_backend is None:
                dist_backend = getattr(params, "dist_backend", None)
            if hop_backend is None:
                hop_backend = getattr(params, "hop_backend", None)
            if patience is None:
                patience = getattr(params, "patience", None)
            if eps is None:
                eps = getattr(params, "eps", None)
        ef = ef or self.params.ef_search
        mode = mode or "while"
        dist_backend = dist_backend or self.params.dist_backend
        rerank = rerank if rerank is not None else self.params.rerank
        hop_backend = hop_backend or self.params.hop_backend
        patience = patience if patience is not None else self.params.patience
        eps = eps if eps is not None else self.params.eps
        with span("index.search"):
            with span("search.project"):
                q = self.project(queries)
            with span("search.entries"):
                entries = self.eps.select(q)
            with span("search.traverse"):
                # batch-major: every hop is one (Q, R) expansion block
                # (the fused beam_hop kernel on TPU)
                bs_kw = dict(ef=max(ef, k), mode=mode,
                             hop_backend=hop_backend,
                             patience=patience or None, eps=eps,
                             with_stats=True)
                if dist_backend == "f32":
                    kb = k
                else:
                    if (self.codec is None
                            or self.codec_backend != dist_backend):
                        self.quantize(dist_backend)
                    # keep enough ADC-ranked survivors for the exact tail to
                    # pick a true top-k from
                    kb = min(max(rerank, k), max(ef, k))
                    with span("search.lut"):
                        lut = self.codec.lut(q)
                    bs_kw.update(dist_backend=dist_backend, codes=self.codes,
                                 lut=lut)
                d, i, stats = beam_search(
                    q, self.base, self.graph.neighbors, entries, k=kb,
                    **bs_kw)
                if dist_backend != "f32":
                    if rerank > 0:
                        with span("search.rerank"):
                            d, i = _exact_rerank(q, self.base, i, k)
                    else:
                        d, i = d[:, :k], i[:, :k]
                self.last_search_stats = stats
            with span("search.ids"):
                orig = jnp.where(i >= 0, self.kept_idx[jnp.maximum(i, 0)],
                                 -1)
        return d, orig

    def search_stats(self) -> Optional[dict]:
        """Per-hop work counters of the latest ``search`` call.

        ``hops`` — total frontier expansions across queries; ``gathered``
        — total candidate rows pulled through the distance stage (valid
        graph edges, pre-dedup); ``dup_gathered`` — how many of those were
        already resident in the pool (wasted gathers). The staged and
        fused hop backends count identically — work-parity assertions in
        the tests compare these dicts across backends.

        Straggler accounting: ``wasted_hops`` — loop iterations lanes rode
        after their own termination (what adaptive termination shrinks);
        ``active_fraction`` —
        hops / (hops + wasted_hops), the useful share of hop-block rows;
        ``mean_hops`` / ``p99_hops`` — the per-query hop distribution whose
        tail is the batch straggler cost.

        ``fetch_share`` — gathered / (R * (hops + wasted_hops)), the share
        of the hop's candidate slots (R per lane per loop iteration) that
        hold a live id; the fused hop fetches a row for those slots only.
        """
        if self.last_search_stats is None:
            return None
        with span("index.stats"):
            # one device-to-host copy of the four counters; the sums run
            # in numpy, so reading them launches no program
            s = jax.device_get(self.last_search_stats)
            hops = np.asarray(s.hops)
            total = int(hops.sum())
            wasted = int(np.sum(s.wasted_hops))
            gathered = int(np.sum(s.gathered))
            slots = self.graph.neighbors.shape[1] * (total + wasted)
            return {"hops": total,
                    "gathered": gathered,
                    "dup_gathered": int(np.sum(s.dup_gathered)),
                    "wasted_hops": wasted,
                    "active_fraction": float(total / max(total + wasted, 1)),
                    "fetch_share": float(gathered / max(slots, 1)),
                    "mean_hops": float(hops.mean()) if hops.size else 0.0,
                    "p99_hops": float(np.percentile(hops, 99))
                    if hops.size else 0.0}

    @property
    def ntotal(self) -> int:
        return 0 if self.base is None else self.base.shape[0]

    @property
    def dim(self) -> int:
        """Query-time input dimensionality (pre-PCA original space)."""
        return self.input_dim

    def search_params_space(self):
        from repro.core.index_api import (
            ef_search_space, patience_space, rerank_space,
        )
        space = ef_search_space()
        if self.params.dist_backend != "f32" or self.codec is not None:
            space = rerank_space(space)
        return patience_space(space)

    def memory_bytes(self) -> int:
        """Index footprint: vectors + graph + entry-point structures +
        quantized codes/codebooks (when a codec is attached)."""
        total = self.base.size * self.base.dtype.itemsize
        total += self.graph.neighbors.size * 4
        total += self.kept_idx.size * 4
        if self.pca is not None:
            total += (self.pca.components.size + self.pca.mean.size) * 4
        total += (self.eps.centroids.size * 4 + self.eps.member_ids.size * 4)
        if self.codes is not None:
            total += self.codes.size * self.codes.dtype.itemsize
        if self.codec is not None:
            total += self.codec.memory_bytes()
        return int(total)

    # -- persistence (core/persist.py) ------------------------------------
    def state_dict(self) -> dict:
        """Complete serving state: base vectors, graph, entry points, PCA,
        codec tables, and the build-time kNN table (kept so ``reprune``
        still works on a restored index). ``from_state`` reconstructs all
        of it verbatim — no k-means, no re-encode, no graph work."""
        assert self.graph is not None, "fit() first"
        arrays = {"kept_idx": self.kept_idx, "base": self.base,
                  "neighbors": self.graph.neighbors,
                  "medoid": self.graph.medoid,
                  "eps_centroids": self.eps.centroids,
                  "eps_member_ids": self.eps.member_ids}
        if self.knn_ids is not None:
            arrays["knn_ids"] = self.knn_ids
        if self.pca is not None:
            arrays["pca_mean"] = self.pca.mean
            arrays["pca_components"] = self.pca.components
            arrays["pca_explained"] = self.pca.explained
        if self.codec is not None:
            arrays["codes"] = self.codes
            if hasattr(self.codec, "codebooks"):
                arrays["codec_codebooks"] = self.codec.codebooks
            else:                                    # int8 scalar codec
                arrays["codec_scale"] = self.codec.scale
                arrays["codec_zero"] = self.codec.zero
        return {"meta": {"params": asdict(self.params),
                         "input_dim": self.input_dim,
                         "codec_backend": self.codec_backend,
                         "build_seconds": self.build_seconds},
                "arrays": arrays}

    @classmethod
    def from_state(cls, state: dict) -> "TunedGraphIndex":
        from repro.core.quant import Int8Codec, PQCodec
        meta, a = state["meta"], state["arrays"]
        idx = cls(IndexParams(**drop_removed_knobs(meta["params"])))
        idx.input_dim = meta["input_dim"]
        idx.build_seconds = float(meta.get("build_seconds", 0.0))
        idx.kept_idx = jnp.asarray(a["kept_idx"])
        idx.base = jnp.asarray(a["base"])
        idx.graph = NSGGraph(neighbors=jnp.asarray(a["neighbors"]),
                             medoid=jnp.asarray(a["medoid"]))
        idx.eps = EntryPointSelector(
            centroids=jnp.asarray(a["eps_centroids"]),
            member_ids=jnp.asarray(a["eps_member_ids"]))
        if "knn_ids" in a:
            idx.knn_ids = jnp.asarray(a["knn_ids"])
        if "pca_mean" in a:
            idx.pca = PCA(mean=jnp.asarray(a["pca_mean"]),
                          components=jnp.asarray(a["pca_components"]),
                          explained=jnp.asarray(a["pca_explained"]))
        backend = meta.get("codec_backend")
        if backend is not None:
            if "codec_codebooks" in a:               # PQ
                books = jnp.asarray(a["codec_codebooks"])
                codec = PQCodec(books.shape[0], books.shape[1])
                codec.codebooks = books
                codec.codes = jnp.asarray(a["codes"])
            else:                                    # int8
                codec = Int8Codec()
                codec.scale = jnp.asarray(a["codec_scale"])
                codec.zero = jnp.asarray(a["codec_zero"])
            idx.codec = codec
            idx.codes = jnp.asarray(a["codes"])
            idx.codec_backend = backend
        return idx


@functools.partial(jax.jit, static_argnames=("k",))
def _exact_rerank(queries: jax.Array, base: jax.Array, ids: jax.Array,
                  k: int):
    """Exact f32 squared-L2 rescoring of the (Q, R') beam survivors -> top-k.

    One gather_dist block over the survivor ids, then a top-k re-sort.
    Padded ids (-1) carry +inf and sort last. The jnp form gathers just
    the Q x R' rows; the Pallas kernel would first copy the whole table
    into its tile layout, and gives the same bits.
    """
    d = _gather_dist(queries, base, ids, backend="jnp")
    neg, pos = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(ids, pos, axis=1)


def build_vanilla_nsg(data: jax.Array, *, degree: int = 32,
                      ef_search: int = 64, **kw) -> TunedGraphIndex:
    """Paper's baseline: no PCA, no subsampling, medoid entry point."""
    p = IndexParams(pca_dim=data.shape[1], antihub_keep=1.0, ep_clusters=1,
                    ef_search=ef_search, graph_degree=degree, **kw)
    return TunedGraphIndex(p).fit(data)
