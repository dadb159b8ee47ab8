"""Sharded graph-index serving + sharded build substrate.

Scale-out scheme (DESIGN.md §2): the database is row-sharded on the `model`
mesh axis; every shard owns an independent NSG sub-graph + entry points.
Queries shard across (`pod`, `data`) and replicate across `model`; each device
beam-searches its local sub-graph, and the per-shard top-k lists (size
shards x k — tiny) merge through one all-gather. No cross-shard pointer
chasing ever happens on the hot path.

Per-shard builds run through the ``core.build`` substrate: the shard's
``IndexParams.knn_backend`` selects exact vs NN-Descent kNN-graph
construction (``"auto"`` flips to NN-Descent once a shard crosses
``build.AUTO_NND_MIN_N`` rows), and ``IndexParams.finish_backend`` selects
the NSG finishing pass (device scatter-min interconnect + batched repair
vs the host numpy parity path, ``core/build/finish.py``) — so sharded
build cost scales with device FLOPs rather than N^2 (or host pointer
chasing) per shard. ``ShardedFactoryIndex`` inherits the same selection
from its spec string (``,ND<K>``) or its own ``knn_backend=`` /
``finish_backend=`` constructor overrides (forwarded to every per-shard
``build_index`` call).

Out-of-core path (this module + ``core/build/{shardlocal,stream}.py``):

  * ``ShardedIndex.fit`` assembles the mesh arrays from per-shard device
    blocks (``row_sharded_from_blocks``) — no ``(shards * m, dim)`` host
    numpy table ever exists, so peak host memory for a sharded fit is one
    shard, not N;
  * ``ShardedIndex.reprune`` runs the whole (alpha, degree) derivation
    *under ``shard_map``* (``build.shardlocal.derive_local``): each device
    reprunes + repairs its own shard in place and the derived neighbors
    table never leaves the mesh;
  * ``StreamedShardedIndex`` is the single-box host-offload tier: shards
    live in host buffers (pinned device memory when the backend has a
    ``pinned_host`` space) and stream through HBM one at a time with
    one-deep prefetch — N is bounded by host RAM, not HBM.
"""
from __future__ import annotations

import copy
import functools
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, replace as dc_replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.beam_search import beam_search, dot_gather_dist
from repro.core.build.shardlocal import derive_local
from repro.core.build.stream import HostOffloadStore
from repro.core.distances import l2_topk
from repro.core.index_api import build_index
from repro.core.pipeline import (
    IndexParams, TunedGraphIndex, drop_removed_knobs,
)
from repro.distributed.sharding import row_sharded_from_blocks


def shard_bounds(n: int, s: int) -> np.ndarray:
    """Exact integer row splits: ``bounds[i] = i * n // s`` (s + 1 edges).

    Shard sizes differ by at most one row and sum to exactly ``n``. The
    previous ``np.linspace(0, n, s + 1).astype(int)`` TRUNCATED the float
    edges, so interior bounds could land a row early, shard sizes drifted
    by more than one, and the ``bounds[i]``-based global-id offsets with
    them — regression-tested over awkward (n, s) pairs.
    """
    return (np.arange(s + 1, dtype=np.int64) * n) // s


def _pad_rows(x: jax.Array, m: int, fill=0) -> jax.Array:
    """Pad the leading dim up to ``m`` rows with a constant (device op)."""
    pad = [(0, m - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


def _sub_stage_stats(sub: "TunedGraphIndex") -> dict:
    """One shard's build-stage timings, flattened for bench artifacts."""
    st = sub.build_stats
    return dict(
        n=int(sub.ntotal),
        build_seconds=float(sub.build_seconds),
        knn_seconds=float(sub.knn_seconds),
        pools_seconds=float(getattr(st, "pools_seconds", 0.0)),
        prune_seconds=float(getattr(st, "prune_seconds", 0.0)),
        finish_seconds=float(getattr(st, "interconnect_seconds", 0.0)
                             + getattr(st, "repair_seconds", 0.0)),
        repair_rounds=int(getattr(st, "repair_rounds", 0)),
    )


def device_array_bytes(obj, _depth: int = 3) -> int:
    """Analytic footprint of every array hanging off ``obj`` (a few levels
    of attribute/field nesting deep) — the generic fallback for index
    families that don't implement ``memory_bytes`` themselves."""
    if hasattr(obj, "nbytes") and hasattr(obj, "dtype"):
        return int(obj.nbytes)
    if _depth <= 0:
        return 0
    if hasattr(obj, "_fields"):                    # NamedTuple
        vals = [getattr(obj, f) for f in obj._fields]
    elif hasattr(obj, "__dict__"):
        vals = list(vars(obj).values())
    elif isinstance(obj, dict):
        vals = list(obj.values())
    elif isinstance(obj, (list, tuple)):
        vals = list(obj)
    else:
        return 0
    return sum(device_array_bytes(v, _depth - 1) for v in vals)


# ---------------------------------------------------------------------------
# Sharded brute force (build substrate + retrieval_cand serving)
# ---------------------------------------------------------------------------


def make_sharded_l2_topk(mesh: Mesh, k: int, chunk: int = 16384):
    """queries (Q, D) x db (N, D; rows sharded on `model`) -> exact top-k.

    Local streaming top-k per shard, then a (Q, shards*k) merge. Queries are
    sharded on the batch axes and replicated across `model`.
    """
    batch = tuple(a for a in mesh.axis_names if a != "model")
    n_shards = int(np.prod([mesh.shape[a] for a in ("model",)]))

    def local(q, db_local, offset):
        d, i = l2_topk(q, db_local, k, chunk=chunk)
        return d, jnp.where(i >= 0, i + offset, -1)

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch, None), P("model", None), P("model")),
        out_specs=(P(batch, "model"), P(batch, "model")))

    @jax.jit
    def search(queries, db, offsets):
        d, i = mapped(queries, db, offsets)          # (Q, shards*k)
        nd, pos = jax.lax.top_k(-d, k)
        return -nd, jnp.take_along_axis(i, pos, axis=1)

    return search


# ---------------------------------------------------------------------------
# Sharded graph index
# ---------------------------------------------------------------------------


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["base", "neighbors", "global_ids", "centroids", "members",
                 "pca_mean", "pca_comp", "base_norms"],
    meta_fields=[])
@dataclass
class ShardedIndexArrays:
    """Flat device arrays; rows [s*m:(s+1)*m] belong to shard s."""
    base: jax.Array        # (S*m, D)   projected vectors (padded)
    neighbors: jax.Array   # (S*m, R)   LOCAL ids, -1 padded
    global_ids: jax.Array  # (S*m,)     original database ids (-1 = pad)
    centroids: jax.Array   # (S*C, D)   entry-point centroids per shard
    members: jax.Array     # (S*C,)     LOCAL entry ids (-1 = padded slot)
    pca_mean: jax.Array    # (D0,)
    pca_comp: jax.Array    # (D0, D)    identity-extended when PCA off
    base_norms: Optional[jax.Array] = None  # (S*m,) |x|^2 (P8 prenorm)


def _local_beam(q, base, nbrs, gids, cents, members, norms, *, ef: int,
                k: int, max_iters: int, mode: str, prenorm: bool):
    """One shard's search: nearest-centroid entry -> beam -> global ids.

    The body shared by the SPMD serve step (under ``shard_map``) and the
    host-offload streaming tier (jitted per shard) — so entry-point
    semantics, prenorm distances, and padding rules cannot diverge.
    """
    qd = q.astype(jnp.float32)
    cd = (jnp.sum(qd * qd, -1, keepdims=True)
          + jnp.sum(cents * cents, -1)[None, :]
          - 2.0 * qd @ cents.T)
    # padded entry slots (members == -1) carry a zero centroid; for
    # centered data the origin can beat every real centroid, which would
    # route the query into row 0 of the wrong shard — mask them out
    cd = jnp.where((members >= 0)[None, :], cd, jnp.inf)
    entry = jnp.maximum(members[jnp.argmin(cd, axis=1)], 0)
    gdist = dot_gather_dist
    if prenorm:
        # P8: |x|^2 precomputed at build; each expansion reads R norms
        # instead of squaring R*D gathered elements
        def one(query, db, ids):
            q32 = query.astype(jnp.float32)
            rows = db[ids].astype(jnp.float32)
            return jnp.maximum(jnp.sum(q32 * q32) + norms[ids]
                               - 2.0 * (rows @ q32), 0.0)
        gdist = jax.vmap(one, in_axes=(0, None, 0))
    d, i, _ = beam_search(q, base, nbrs, entry, ef=ef, k=k,
                          max_iters=max_iters or 4 * ef, mode=mode,
                          gather_dist=gdist)
    gi = jnp.where(i >= 0, gids[jnp.maximum(i, 0)], -1)
    d = jnp.where(gi >= 0, d, jnp.inf)
    return d, gi


_stream_local = functools.partial(
    jax.jit, static_argnames=("ef", "k", "max_iters", "mode", "prenorm")
)(_local_beam)


def make_search_step(mesh: Mesh, *, ef: int, k: int, max_iters: int = 0,
                     mode: str = "fori"):
    """Build the jit'd sharded serve step (also the dry-run target).

    Returns fn(queries (Q, D0), arrays) -> (dists (Q, k), global ids (Q, k)).
    """
    from repro import flags
    if not max_iters and flags.ANN_TIGHT_BUDGET:
        max_iters = 2 * ef      # P4: converged budget (recall-validated)
    batch = tuple(a for a in mesh.axis_names if a != "model")

    local_search = functools.partial(
        _local_beam, ef=ef, k=k, max_iters=max_iters, mode=mode,
        prenorm=flags.ANN_PRENORM)

    mapped = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(P(batch, None), P("model", None), P("model", None),
                  P("model"), P("model", None), P("model"), P("model")),
        out_specs=(P(batch, "model"), P(batch, "model")))

    @jax.jit
    def step(queries, arrays: ShardedIndexArrays):
        q = (queries - arrays.pca_mean) @ arrays.pca_comp
        norms = arrays.base_norms
        if norms is None:
            norms = jnp.sum(arrays.base.astype(jnp.float32) ** 2, axis=-1)
        d, i = mapped(q, arrays.base, arrays.neighbors, arrays.global_ids,
                      arrays.centroids, arrays.members, norms)
        nd, pos = jax.lax.top_k(-d, k)               # (Q, shards*k) -> (Q, k)
        return -nd, jnp.take_along_axis(i, pos, axis=1)

    return step


def _shard_blocks(sub: TunedGraphIndex, *, m: int, c: int, offset: int,
                  mean, comp, base_dt) -> dict:
    """One fitted shard -> equal-shape device blocks (padded to m rows).

    All device ops, all shard-sized: re-projects the shard's base with the
    GLOBAL (shard-0) PCA transform, pads rows/centroid slots, and derives
    the prenorm |x|^2 row. ``members`` pads with -1 — the serve step masks
    those entry slots to +inf (see ``_local_beam``).
    """
    b = sub.base
    if sub.pca is not None:
        b = (sub.pca.inverse_transform(b) - mean) @ comp
    b = _pad_rows(b.astype(jnp.float32), m)
    return dict(
        base=b.astype(base_dt),
        neighbors=_pad_rows(sub.graph.neighbors.astype(jnp.int32), m, -1),
        global_ids=_pad_rows(
            sub.kept_idx.astype(jnp.int32) + jnp.int32(offset), m, -1),
        centroids=_pad_rows(sub.eps.centroids.astype(jnp.float32), c),
        members=_pad_rows(sub.eps.member_ids.astype(jnp.int32), c, -1),
        base_norms=jnp.sum(b * b, axis=-1),
        knn_ids=_pad_rows(sub.knn_ids.astype(jnp.int32), m, -1),
        medoid=sub.graph.medoid.astype(jnp.int32)[None],
    )


class ShardedIndex:
    """Host-orchestrated build of per-shard TunedGraphIndexes + device search.

    The per-shard builds are independent (they run as separate jit programs,
    i.e. on a real cluster each host builds its own shards in parallel); the
    search path is one SPMD program over the whole mesh. Assembly places
    per-shard device blocks directly (``row_sharded_from_blocks``) and the
    rebuild-free reprune derives shard-locally under ``shard_map`` — no
    N-proportional host array exists on either path.
    """

    def __init__(self, params: IndexParams, mesh: Mesh):
        self.params = params
        self.mesh = mesh
        self.arrays: Optional[ShardedIndexArrays] = None
        self._step = None
        # retained per-shard indexes (their cached max-degree graphs back
        # host-side consumers; the mesh reprune path below doesn't touch
        # them)
        self.subs: list = []
        self._m = 0                       # per-shard padded row count
        self.n_structural_builds = 0      # per-shard fits ever run here
        # mesh-resident structural substrate for shard-local reprune:
        # the fit-time max-degree adjacency + kNN parents + per-shard
        # medoids (derived clones share these with their parent)
        self.struct_neighbors: Optional[jax.Array] = None
        self.knn_ids: Optional[jax.Array] = None
        self.medoids: Optional[jax.Array] = None

    @property
    def n_shards(self) -> int:
        return self.mesh.shape["model"]

    def fit(self, data: jax.Array, key: Optional[jax.Array] = None):
        key = key if key is not None else jax.random.PRNGKey(0)
        p = self.params
        n, d0 = data.shape
        s = self.n_shards
        bounds = shard_bounds(n, s)
        # shard i builds on (and keeps its blocks on) the first device of
        # its `model` column, so no device holds more than its own shard;
        # the shard builds are independent, so they run concurrently, one
        # host thread per device
        home = np.moveaxis(
            self.mesh.devices, self.mesh.axis_names.index("model"), 0
        ).reshape(s, -1)[:, 0]

        def fit_shard(i):
            rows = data[int(bounds[i]):int(bounds[i + 1])]
            return TunedGraphIndex(p).fit(jax.device_put(rows, home[i]),
                                          jax.random.fold_in(key, i))

        with ThreadPoolExecutor(max_workers=s) as pool:
            subs = list(pool.map(fit_shard, range(s)))
        self.subs = subs
        self.n_structural_builds += s
        m = max(sub.ntotal for sub in subs)
        self._m = m
        dim = subs[0].base.shape[1]
        c = p.ep_clusters
        # PCA is shard-local in principle; we broadcast shard 0's
        # projection to keep the query-side transform global (all shards
        # were fit on slices of one distribution — verified equivalent
        # within tolerance), re-projecting every shard's base on device.
        if subs[0].pca is not None:
            mean = subs[0].pca.mean.astype(jnp.float32)
            comp = subs[0].pca.components.astype(jnp.float32)
        else:
            mean = jnp.zeros((d0,), jnp.float32)
            comp = jnp.eye(d0, dim, dtype=jnp.float32)

        from repro import flags
        base_dt = jnp.bfloat16 if flags.ANN_BF16_BASE else jnp.float32
        blocks = [_shard_blocks(sub, m=m, c=c, offset=int(bounds[i]),
                                mean=jax.device_put(mean, home[i]),
                                comp=jax.device_put(comp, home[i]),
                                base_dt=base_dt)
                  for i, sub in enumerate(subs)]

        def rows(field, *trailing):
            return row_sharded_from_blocks(
                self.mesh, [b[field] for b in blocks], *trailing)

        self.arrays = ShardedIndexArrays(
            base=rows("base", None),
            neighbors=rows("neighbors", None),
            global_ids=rows("global_ids"),
            centroids=rows("centroids", None),
            members=rows("members"),
            pca_mean=jax.device_put(mean, NamedSharding(self.mesh, P())),
            pca_comp=jax.device_put(comp, NamedSharding(self.mesh, P())),
            base_norms=rows("base_norms"),
        )
        self.struct_neighbors = self.arrays.neighbors
        self.knn_ids = rows("knn_ids", None)
        self.medoids = rows("medoid")
        return self

    # -- rebuild-free derivation ("prune, don't rebuild", sharded) --------
    def reprune(self, *, alpha: float = 1.0,
                degree: Optional[int] = None) -> "ShardedIndex":
        """Derive an (alpha, degree) variant with NO per-shard rebuild.

        The whole derivation (distance-sorted adjacency -> α-RNG occlusion
        scan -> connectivity repair, ``build.shardlocal.derive_local``)
        runs under ``shard_map``: each device reprunes its own shard from
        the mesh-resident structural (max-degree) adjacency and the
        derived neighbors table is born sharded — nothing round-trips
        through the host. Every other device array (base vectors, ids,
        centroids, norms, PCA) is shared with the parent, and chained
        reprunes re-derive from the same structural substrate (degree can
        go back UP on a derived index). ``n_structural_builds`` is
        inherited unchanged — the no-rebuild property tests assert on it.
        """
        assert self.arrays is not None, "fit() first"
        rmax = self.struct_neighbors.shape[1]
        r_out = rmax if degree is None else min(degree, rmax)

        def local(base, snbrs, knn, med, gids, a):
            return derive_local(base, snbrs, knn, med[0], gids >= 0,
                                alpha=a[0], degree=r_out)

        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P("model", None), P("model", None), P("model", None),
                      P("model"), P("model"), P()),
            out_specs=P("model", None))
        nbrs = jax.jit(mapped)(
            self.arrays.base, self.struct_neighbors, self.knn_ids,
            self.medoids, self.arrays.global_ids,
            jnp.asarray([alpha], jnp.float32))
        out = copy.copy(self)
        out.params = dc_replace(self.params, alpha=alpha,
                                graph_degree=r_out)
        out.arrays = dc_replace(self.arrays, neighbors=nbrs)
        return out

    def search(self, queries: jax.Array, k: int, params=None, *,
               ef: Optional[int] = None, mode: Optional[str] = None):
        if params is not None:
            ef = ef if ef is not None else params.ef_search
            mode = mode if mode is not None else params.mode
        skey = (ef or self.params.ef_search, k, mode or "while")
        # cache the jitted step per (ef, k, mode): rebuilding it per call
        # would hand every QPS measurement a cold trace cache (the step
        # closes over no arrays, so derived reprune clones share it)
        if self._step is None or self._step[0] != skey:
            self._step = (skey, make_search_step(
                self.mesh, ef=skey[0], k=k, mode=skey[2]))
        return self._step[1](queries, self.arrays)

    @property
    def shard_stats(self) -> list:
        """Per-shard build-stage timings (knn/pools/prune/finish seconds)
        — what ``launch/tune --bench-build-out`` aggregates."""
        return [_sub_stage_stats(sub) for sub in self.subs]

    @property
    def ntotal(self) -> int:
        if self.arrays is None:
            return 0
        return int((np.asarray(self.arrays.global_ids) >= 0).sum())

    @property
    def dim(self) -> int:
        return 0 if self.arrays is None else self.arrays.pca_mean.shape[0]

    def search_params_space(self):
        from repro.core.index_api import ef_search_space
        return ef_search_space()

    def memory_bytes(self) -> int:
        """Mesh-resident footprint, counted analytically over the device
        arrays (serving set + the structural reprune substrate). Arrays
        shared between a parent and its derived clones are the same
        buffers, so each is counted once per index, not per alias."""
        if self.arrays is None:
            return 0
        seen, total = set(), 0
        leaves = list(jax.tree_util.tree_leaves(self.arrays))
        leaves += [self.struct_neighbors, self.knn_ids, self.medoids]
        for leaf in leaves:
            if leaf is None or id(leaf) in seen:
                continue
            seen.add(id(leaf))
            total += int(leaf.nbytes)
        return total


# ---------------------------------------------------------------------------
# Host-offload tier: build and serve N >> HBM on one box
# ---------------------------------------------------------------------------


class StreamedShardedIndex:
    """Out-of-core single-box tier: shards parked in host buffers.

    Same per-shard pipeline as ``ShardedIndex``, but instead of living on
    a device mesh the fitted shards are offloaded to a
    ``HostOffloadStore`` (pinned-host device memory when the backend has a
    distinct host space, numpy otherwise). Build, search, and reprune all
    stream the shards through the device one at a time with one-deep
    prefetch — device residency is bounded at two shards and host
    residency at the store, so N is capped by host RAM, not HBM.

    Search merges the per-shard top-k exactly like the SPMD path (the
    local step is literally the same ``_local_beam``); reprune runs the
    same ``derive_local`` program the ``shard_map`` path uses, shard by
    shard, and shares every non-derived host buffer with the parent.
    """

    def __init__(self, params: IndexParams, n_shards: int = 2):
        self.params = params
        self.n_shards = n_shards
        self.store = HostOffloadStore()
        self._structural: Optional[HostOffloadStore] = None
        self.pca_mean: Optional[jax.Array] = None
        self.pca_comp: Optional[jax.Array] = None
        self._m = 0
        self.input_dim = 0
        self.n_structural_builds = 0
        # per-shard build-stage timings, recorded before each sub is
        # dropped (the sub itself never outlives its offload)
        self.shard_stats: list = []

    def fit(self, data, key: Optional[jax.Array] = None):
        key = key if key is not None else jax.random.PRNGKey(0)
        p = self.params
        n, d0 = data.shape
        self.input_dim = d0
        bounds = shard_bounds(n, self.n_shards)
        # two passes would need all subs live at once to know m; instead
        # shard sizes differ by <= 1 row, so m is known up front and each
        # sub can be BUILT, offloaded, and dropped before the next starts
        m = -(-n // self.n_shards)
        self._m = m
        mean = comp = None
        from repro import flags
        base_dt = jnp.bfloat16 if flags.ANN_BF16_BASE else jnp.float32
        for i in range(self.n_shards):
            sub = TunedGraphIndex(p).fit(
                jnp.asarray(data[int(bounds[i]):int(bounds[i + 1])]),
                jax.random.fold_in(key, i))
            self.n_structural_builds += 1
            if i == 0:
                if sub.pca is not None:
                    mean = sub.pca.mean.astype(jnp.float32)
                    comp = sub.pca.components.astype(jnp.float32)
                else:
                    dim = sub.base.shape[1]
                    mean = jnp.zeros((d0,), jnp.float32)
                    comp = jnp.eye(d0, dim, dtype=jnp.float32)
                self.pca_mean, self.pca_comp = mean, comp
            self.store.offload(i, _shard_blocks(
                sub, m=m, c=p.ep_clusters, offset=int(bounds[i]),
                mean=mean, comp=comp, base_dt=base_dt))
            self.shard_stats.append(_sub_stage_stats(sub))
            del sub             # drop device references -> frees HBM
        self._structural = self.store
        return self

    def reprune(self, *, alpha: float = 1.0,
                degree: Optional[int] = None) -> "StreamedShardedIndex":
        """Streamed rebuild-free derivation: fetch shard, ``derive_local``
        on device, offload the derived neighbors — host buffers other
        than the neighbors table are shared with the parent."""
        assert self._structural is not None, "fit() first"
        rmax = np.asarray(
            self._structural.peek_host(0)["neighbors"]).shape[1]
        r_out = rmax if degree is None else min(degree, rmax)
        out = copy.copy(self)
        out.store = HostOffloadStore()
        out.params = dc_replace(self.params, alpha=alpha,
                                graph_degree=r_out)
        self._structural.prefetch(0)
        for i in range(self.n_shards):
            if i + 1 < self.n_shards:
                self._structural.prefetch(i + 1)
            t = self._structural.fetch(i)
            nbrs = derive_local(
                t["base"], t["neighbors"], t["knn_ids"], t["medoid"][0],
                t["global_ids"] >= 0, alpha=alpha, degree=r_out)
            out.store.offload(i, dict(
                self._structural.peek_host(i), neighbors=nbrs))
        return out

    def search(self, queries: jax.Array, k: int, params=None, *,
               ef: Optional[int] = None, mode: Optional[str] = None):
        from repro import flags
        if params is not None:
            ef = ef if ef is not None else params.ef_search
            mode = mode if mode is not None else params.mode
        ef = ef or self.params.ef_search
        mode = mode or "while"
        max_iters = 2 * ef if flags.ANN_TIGHT_BUDGET else 4 * ef
        q = (queries - self.pca_mean) @ self.pca_comp
        dists, ids = [], []
        self.store.prefetch(0)
        for i in range(self.n_shards):
            if i + 1 < self.n_shards:
                # stage the NEXT shard's H2D transfer before this shard's
                # search is dispatched — on an async backend they overlap
                self.store.prefetch(i + 1)
            t = self.store.fetch(i)
            d, gi = _stream_local(
                q, t["base"], t["neighbors"], t["global_ids"],
                t["centroids"], t["members"], t["base_norms"],
                ef=ef, k=k, max_iters=max_iters, mode=mode,
                prenorm=flags.ANN_PRENORM)
            dists.append(d)
            ids.append(gi)
        d = jnp.concatenate(dists, axis=1)          # (Q, shards*k)
        i = jnp.concatenate(ids, axis=1)
        nd, pos = jax.lax.top_k(-d, k)
        return -nd, jnp.take_along_axis(i, pos, axis=1)

    @property
    def ntotal(self) -> int:
        total = 0
        for key in self.store.keys():
            gids = np.asarray(self.store.peek_host(key)["global_ids"])
            total += int((gids >= 0).sum())
        return total

    @property
    def dim(self) -> int:
        return self.input_dim

    def search_params_space(self):
        from repro.core.index_api import ef_search_space
        return ef_search_space()

    def memory_bytes(self) -> int:
        total = self.store.nbytes()
        if self._structural is not None and self._structural is not self.store:
            # derived clone: only the neighbors leaf differs; the shared
            # host buffers are counted once via the structural store
            total = self._structural.nbytes()
            for key in self.store.keys():
                nbrs = self.store.peek_host(key)["neighbors"]
                total += int(np.asarray(nbrs).nbytes)
        if self.pca_mean is not None:
            total += int(self.pca_mean.nbytes) + int(self.pca_comp.nbytes)
        return total


# ---------------------------------------------------------------------------
# Generic sharding over the Index protocol
# ---------------------------------------------------------------------------


class ShardedFactoryIndex:
    """Row-shard ANY registered index family behind the unified API.

    Host-orchestrated scale-out: rows split evenly across ``n_shards``, one
    independent sub-index per shard built from the same factory spec
    (``build_index``), search fans the query batch out to every sub-index and
    merges the per-shard top-k lists (size shards * k — tiny). Conforms to
    the ``Index`` protocol itself, so sharding composes with everything else
    (generic tuner, serve steps, benchmarks).

    A ``PCA<d>`` prefix is hoisted out of the per-shard spec and fit ONCE on
    the full dataset: per-shard projections would span different subspaces,
    making the merged distances incomparable (a shard whose projection
    discards more variance would win merge slots it shouldn't).

    ``ShardedIndex`` above remains the SPMD fast path specialized to the
    paper's graph pipeline; this wrapper trades one fused program for total
    generality (IVF/PQ/HNSW/Flat shards all work).
    """

    def __init__(self, spec: str, n_shards: int = 2,
                 knn_backend: Optional[str] = None,
                 finish_backend: Optional[str] = None,
                 dist_backend: Optional[str] = None,
                 rerank: Optional[int] = None,
                 hop_backend: Optional[str] = None,
                 patience: Optional[int] = None,
                 eps: Optional[float] = None,
                 on_shard_error: str = "raise"):
        if on_shard_error not in ("raise", "skip"):
            raise ValueError(
                f"on_shard_error must be 'raise' or 'skip', "
                f"got {on_shard_error!r}")
        self.spec = spec
        self.n_shards = n_shards
        self.knn_backend = knn_backend         # per-shard build override
        self.finish_backend = finish_backend   # per-shard finish override
        self.dist_backend = dist_backend       # per-shard serving precision
        self.rerank = rerank                   # per-shard exact-rerank depth
        self.hop_backend = hop_backend         # per-shard beam-hop backend
        self.patience = patience               # per-shard adaptive patience
        self.eps = eps                         # per-shard progress threshold
        self.on_shard_error = on_shard_error   # degraded-search default
        self.degraded_shards = 0               # failed shards, last search
        self.last_shard_errors: list = []      # (shard, exception) of same
        self.subs: list = []
        # the max-degree shards fit() built: reprune always derives from
        # these (NOT from self.subs, which on a derived index are already
        # pruned), so chained reprunes never compound
        self._structural_subs: list = []
        self.offsets: Optional[np.ndarray] = None
        self.pca = None
        self.input_dim: int = 0
        self.n_structural_builds = 0     # per-shard fits ever run here

    def fit(self, data: jax.Array, *, key: Optional[jax.Array] = None):
        from repro.core.index_api import split_pca_prefix
        from repro.core.pca import fit_pca
        key = key if key is not None else jax.random.PRNGKey(0)
        self.input_dim = data.shape[1]
        pca_dim, inner_spec = split_pca_prefix(self.spec)
        if pca_dim is not None:
            self.pca = fit_pca(data, pca_dim)
            data = self.pca.transform(data)
        n = data.shape[0]
        bounds = shard_bounds(n, self.n_shards)
        self.offsets = bounds[:-1]
        self.subs = [
            build_index(inner_spec, data[bounds[i]:bounds[i + 1]],
                        key=jax.random.fold_in(key, i),
                        knn_backend=self.knn_backend,
                        finish_backend=self.finish_backend,
                        dist_backend=self.dist_backend,
                        rerank=self.rerank,
                        hop_backend=self.hop_backend,
                        patience=self.patience,
                        eps=self.eps)
            for i in range(self.n_shards)
        ]
        self._structural_subs = self.subs
        self.n_structural_builds += self.n_shards
        return self

    def reprune(self, *, alpha: float = 1.0,
                degree: Optional[int] = None) -> "ShardedFactoryIndex":
        """Per-shard rebuild-free (alpha, degree) derivation.

        Works for any spec whose family supports ``reprune`` (the NSG
        pipeline); shards share their base vectors with the parent, only
        the serving graphs are derived. Raises TypeError for families
        without a cached max-degree graph.
        """
        if not self._structural_subs:
            raise RuntimeError("fit() first")
        if not all(hasattr(s, "reprune") for s in self._structural_subs):
            raise TypeError(
                f"spec {self.spec!r} shards do not support reprune "
                "(graph-family specs only)")
        out = copy.copy(self)
        out.subs = [s.reprune(alpha=alpha, degree=degree)
                    for s in self._structural_subs]
        return out

    def search(self, queries: jax.Array, k: int, params=None, *,
               on_shard_error: Optional[str] = None):
        """Fan out to every shard, merge the per-shard top-k.

        ``on_shard_error`` ("raise" | "skip", default the constructor's
        setting) is the degraded-search contract: under "skip" a shard
        whose search raises contributes +inf-distance / -1-id lanes — the
        merge already treats those as non-answers, so the result is the
        exact top-k over the SURVIVING shards' rows (correct, possibly
        missing true neighbors that lived on the dead shard). The failure
        is accounted, never hidden: ``degraded_shards`` counts dropped
        shards and ``last_shard_errors`` keeps (shard, exception) pairs
        for the serving layer to report.
        """
        mode = on_shard_error or self.on_shard_error
        if mode not in ("raise", "skip"):
            raise ValueError(
                f"on_shard_error must be 'raise' or 'skip', got {mode!r}")
        if self.pca is not None:
            queries = self.pca.transform(queries)
        nq = queries.shape[0]
        dists, ids = [], []
        self.degraded_shards = 0
        self.last_shard_errors = []
        for shard, (off, sub) in enumerate(zip(self.offsets, self.subs)):
            try:
                d, i = sub.search(queries, k, params)
            except Exception as e:
                if mode == "raise":
                    raise
                self.degraded_shards += 1
                self.last_shard_errors.append((shard, e))
                d = jnp.full((nq, k), jnp.inf, jnp.float32)
                i = jnp.full((nq, k), -1, jnp.int32)
            dists.append(d)
            ids.append(jnp.where(i >= 0, i + int(off), -1))
        if self.degraded_shards == len(self.subs):
            raise RuntimeError(
                f"all {len(self.subs)} shards failed; no degraded result "
                f"is possible (first: {self.last_shard_errors[0][1]!r})")
        d = jnp.concatenate(dists, axis=1)          # (Q, shards*k)
        i = jnp.concatenate(ids, axis=1)
        d = jnp.where(i >= 0, d, jnp.inf)
        nd, pos = jax.lax.top_k(-d, k)
        return -nd, jnp.take_along_axis(i, pos, axis=1)

    @property
    def ntotal(self) -> int:
        return sum(s.ntotal for s in self.subs)

    @property
    def dim(self) -> int:
        return self.input_dim

    def search_params_space(self):
        # all shards share a spec, hence a knob space; pre-fit, derive it
        # from the spec like every other conformer does
        if self.subs:
            return self.subs[0].search_params_space()
        from repro.core.index_api import parse_spec
        _, unfitted = parse_spec(self.spec, max(self.input_dim, 1))
        return unfitted.search_params_space()

    def memory_bytes(self) -> int:
        """Per-shard footprints + the hoisted PCA. Shards implementing
        ``memory_bytes`` report themselves; for the rest the device
        arrays are counted analytically (``device_array_bytes``) instead
        of silently contributing 0."""
        total = 0
        for s in self.subs:
            fn = getattr(s, "memory_bytes", None)
            total += int(fn()) if callable(fn) else device_array_bytes(s)
        if self.pca is not None:
            total += (self.pca.components.size + self.pca.mean.size) * 4
        return total

    # -- persistence (core/persist.py) ------------------------------------
    def state_dict(self) -> dict:
        from repro.core.persist import index_state
        arrays: dict = {"offsets": np.asarray(self.offsets, np.int64)}
        subs_meta = []
        for si, sub in enumerate(self.subs):
            st = index_state(sub)
            subs_meta.append({"family": st["family"], "meta": st["meta"]})
            arrays.update({f"sub{si}/{k}": v
                           for k, v in st["arrays"].items()})
        if self.pca is not None:
            arrays["pca_mean"] = self.pca.mean
            arrays["pca_components"] = self.pca.components
            arrays["pca_explained"] = self.pca.explained
        meta = {"spec": self.spec, "n_shards": self.n_shards,
                "input_dim": self.input_dim,
                "on_shard_error": self.on_shard_error,
                "overrides": {
                    "knn_backend": self.knn_backend,
                    "finish_backend": self.finish_backend,
                    "dist_backend": self.dist_backend,
                    "rerank": self.rerank,
                    "hop_backend": self.hop_backend,
                    "patience": self.patience,
                    "eps": self.eps},
                "subs": subs_meta}
        return {"meta": meta, "arrays": arrays}

    @classmethod
    def from_state(cls, state: dict) -> "ShardedFactoryIndex":
        from repro.core.index_api import split_pca_prefix
        from repro.core.pca import PCA
        from repro.core.persist import index_from_state
        meta, a = state["meta"], state["arrays"]
        idx = cls(meta["spec"], n_shards=meta["n_shards"],
                  on_shard_error=meta.get("on_shard_error", "raise"),
                  **drop_removed_knobs(meta["overrides"]))
        idx.input_dim = meta["input_dim"]
        idx.offsets = np.asarray(a["offsets"])
        if "pca_mean" in a:
            idx.pca = PCA(mean=jnp.asarray(a["pca_mean"]),
                          components=jnp.asarray(a["pca_components"]),
                          explained=jnp.asarray(a["pca_explained"]))
        _, inner_spec = split_pca_prefix(meta["spec"])
        idx.subs = []
        for si, sub_meta in enumerate(meta["subs"]):
            sub = index_from_state({
                "family": sub_meta["family"], "meta": sub_meta["meta"],
                "arrays": {k[len(f"sub{si}/"):]: v for k, v in a.items()
                           if k.startswith(f"sub{si}/")}})
            sub.spec = inner_spec
            idx.subs.append(sub)
        idx._structural_subs = idx.subs
        return idx


def input_specs_for_search(cfg, batch: int, n_candidates: int,
                           n_shards: int) -> dict:
    """ShapeDtypeStructs for the ANN serve_step dry-run (no allocation)."""
    from repro import flags
    dim = cfg.pca_dim
    m = -(-n_candidates // n_shards)
    n_rows = n_shards * m
    f32, i32 = jnp.float32, jnp.int32
    base_dt = jnp.bfloat16 if flags.ANN_BF16_BASE else f32  # P3
    sd = jax.ShapeDtypeStruct
    return dict(
        queries=sd((batch, cfg.dim), f32),
        arrays=ShardedIndexArrays(
            base=sd((n_rows, dim), base_dt),
            neighbors=sd((n_rows, cfg.graph_degree), i32),
            global_ids=sd((n_rows,), i32),
            centroids=sd((n_shards * cfg.ep_clusters, dim), f32),
            members=sd((n_shards * cfg.ep_clusters,), i32),
            pca_mean=sd((cfg.dim,), f32),
            pca_comp=sd((cfg.dim, dim), f32),
            base_norms=sd((n_rows,), f32),
        ),
    )
