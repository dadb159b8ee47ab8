"""HNSW — paper Fig. 1 baseline ("HNSW32,Flat"), device-resident search.

Hierarchical navigable small world graph (Malkov & Yashunin). The build is
the classic sequential greedy-insert (host numpy, exactly like the original).
Search is batch-native end to end:

  * the upper layers are stacked into one padded (L, N, m) device table at
    fit time, and the greedy entry-point descent for a whole query batch is
    a single jitted call (`vmap` over a per-layer `lax.while_loop`) — zero
    per-query host loops;
  * with ``ep_clusters > 1`` the paper's §3.1 entry-point knob replaces the
    hierarchy: k-means representatives are fit at build time and selected
    per query in one device call (spec ``HNSW32,EP16``);
  * layer-0 search is the batch-major TPU beam kernel from beam_search.
"""
from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.beam_search import _sqdist_rows, beam_search
from repro.core.entry_points import EntryPointSelector, fit_entry_points


@jax.jit
def _descend_upper(queries: jax.Array, db: jax.Array, upper: jax.Array,
                   entry: jax.Array) -> jax.Array:
    """Greedy descent through the stacked upper layers, whole batch at once.

    queries: (Q, D); db: (N, D); upper: (L, N, m) int32 (-1 padded, row li
    holding graph layer li+1); entry: () int32 top-level entry node.
    Returns (Q,) int32 layer-0 entry ids.
    """
    n_layers = upper.shape[0]

    def one(q):
        d0 = _sqdist_rows(q, db[entry][None, :])[0]

        def layer_step(i, carry):
            table = upper[n_layers - 1 - i]          # descend top -> layer 1

            def body(s):
                cur, cur_d, _ = s
                nbrs = table[cur]                    # (m,)
                valid = nbrs >= 0
                safe = jnp.where(valid, nbrs, 0)
                d = jnp.where(valid, _sqdist_rows(q, db[safe]), jnp.inf)
                j = jnp.argmin(d)
                better = d[j] < cur_d
                return (jnp.where(better, safe[j], cur).astype(jnp.int32),
                        jnp.where(better, d[j], cur_d), better)

            cur, cur_d, _ = jax.lax.while_loop(
                lambda s: s[2], body, carry + (True,))
            return cur, cur_d

        cur, _ = jax.lax.fori_loop(0, n_layers, layer_step,
                                   (entry.astype(jnp.int32), d0))
        return cur

    return jax.vmap(one)(queries)


class HNSWIndex:
    def __init__(self, m: int = 32, ef_construction: int = 64,
                 ef_search: int = 64, seed: int = 0, ep_clusters: int = 0):
        self.m = m
        self.m0 = 2 * m
        self.ef_c = ef_construction
        self.ef_s = ef_search
        self.ep_clusters = ep_clusters
        self.rng = np.random.default_rng(seed)
        self.layers: List[np.ndarray] = []     # [L][n, deg] neighbor ids
        self.node_level: Optional[np.ndarray] = None
        self.entry: int = 0
        self.data: Optional[np.ndarray] = None
        self.eps: Optional[EntryPointSelector] = None
        # device-resident search state (built by _finalize_device)
        self._db: Optional[jax.Array] = None
        self._nbr0: Optional[jax.Array] = None
        self._upper: Optional[jax.Array] = None

    # -- build (host, sequential greedy insert) ---------------------------
    def fit(self, data: jax.Array, *, key=None):
        # key seeds the optional entry-point k-means; build randomness comes
        # from the constructor's seed-ed generator.
        x = np.asarray(data, np.float32)
        n = x.shape[0]
        self.data = x
        ml = 1.0 / math.log(self.m)
        levels = np.minimum(
            (-np.log(self.rng.uniform(size=n)) * ml).astype(np.int64), 8)
        max_level = int(levels.max())
        self.node_level = levels
        self.layers = [np.full((n, self.m0 if l == 0 else self.m), -1,
                               np.int32) for l in range(max_level + 1)]
        order = np.arange(n)
        self.entry = int(order[np.argmax(levels)])
        inserted: List[int] = []
        for i in order:
            self._insert(int(i), x, levels[int(i)], inserted)
            inserted.append(int(i))
        self._finalize_device(key)
        return self

    def _device_tables(self):
        """Move everything the search path touches onto the device once."""
        self._db = jnp.asarray(self.data)
        self._nbr0 = jnp.asarray(self.layers[0])
        if len(self.layers) > 1:
            self._upper = jnp.stack(
                [jnp.asarray(layer) for layer in self.layers[1:]])
        else:
            self._upper = jnp.full((0, self.data.shape[0], self.m), -1,
                                   jnp.int32)

    def _finalize_device(self, key=None):
        self._device_tables()
        if self.ep_clusters > 1:
            key = key if key is not None else jax.random.PRNGKey(0)
            self.eps = fit_entry_points(key, self._db, self.ep_clusters)

    def _greedy(self, q: np.ndarray, start: int, layer: np.ndarray) -> int:
        cur = start
        cur_d = float(((self.data[cur] - q) ** 2).sum())
        improved = True
        while improved:
            improved = False
            nbrs = layer[cur]
            nbrs = nbrs[nbrs >= 0]
            if len(nbrs) == 0:
                break
            d = ((self.data[nbrs] - q) ** 2).sum(1)
            j = int(np.argmin(d))
            if d[j] < cur_d:
                cur, cur_d = int(nbrs[j]), float(d[j])
                improved = True
        return cur

    def _search_layer(self, q, entry, layer, ef) -> List[int]:
        visited = {entry}
        d0 = float(((self.data[entry] - q) ** 2).sum())
        cand = [(d0, entry)]
        best = [(d0, entry)]
        while cand:
            cand.sort()
            d, u = cand.pop(0)
            if d > max(b[0] for b in best):
                break
            for v in layer[u]:
                if v < 0 or v in visited:
                    continue
                visited.add(int(v))
                dv = float(((self.data[v] - q) ** 2).sum())
                if len(best) < ef or dv < max(b[0] for b in best):
                    cand.append((dv, int(v)))
                    best.append((dv, int(v)))
                    best.sort()
                    best[:] = best[:ef]
        return [u for _, u in best]

    def _insert(self, i: int, x: np.ndarray, level: int,
                inserted: List[int]):
        if not inserted:
            return
        q = x[i]
        cur = self.entry
        top = int(self.node_level[self.entry])
        for l in range(top, level, -1):
            if l < len(self.layers):
                cur = self._greedy(q, cur, self.layers[l])
        for l in range(min(level, top), -1, -1):
            cands = self._search_layer(q, cur, self.layers[l], self.ef_c)
            deg = self.m0 if l == 0 else self.m
            sel = self._select(q, cands, deg)
            self.layers[l][i, :len(sel)] = sel
            for v in sel:                       # reverse edges with prune
                row = self.layers[l][v]
                free = np.nonzero(row < 0)[0]
                if free.size:
                    row[free[0]] = i
                else:
                    ds = ((x[row] - x[v]) ** 2).sum(1)
                    di = ((x[i] - x[v]) ** 2).sum()
                    worst = int(np.argmax(ds))
                    if di < ds[worst]:
                        row[worst] = i
            cur = sel[0] if sel else cur
        if level > int(self.node_level[self.entry]):
            self.entry = i

    def _select(self, q, cands: List[int], deg: int) -> List[int]:
        d = ((self.data[cands] - q) ** 2).sum(1)
        order = np.argsort(d)
        return [int(cands[j]) for j in order[:deg]]

    @property
    def ntotal(self) -> int:
        return 0 if self.data is None else self.data.shape[0]

    @property
    def dim(self) -> int:
        return 0 if self.data is None else self.data.shape[1]

    def search_params_space(self):
        from repro.core.index_api import ef_search_space
        return ef_search_space()

    def memory_bytes(self) -> int:
        total = int(self.data.size * 4
                    + sum(layer.size for layer in self.layers) * 4)
        if self.eps is not None:
            total += int((self.eps.centroids.size
                          + self.eps.member_ids.size) * 4)
        return total

    # -- persistence (core/persist.py) ------------------------------------
    def state_dict(self) -> dict:
        arrays = {"data": self.data, "node_level": self.node_level}
        for li, layer in enumerate(self.layers):
            arrays[f"layer_{li}"] = layer
        if self.eps is not None:
            arrays["eps_centroids"] = self.eps.centroids
            arrays["eps_member_ids"] = self.eps.member_ids
        return {"meta": {"m": self.m, "ef_construction": self.ef_c,
                         "ef_search": self.ef_s,
                         "ep_clusters": self.ep_clusters,
                         "entry": int(self.entry),
                         "n_layers": len(self.layers)},
                "arrays": arrays}

    @classmethod
    def from_state(cls, state: dict) -> "HNSWIndex":
        meta, a = state["meta"], state["arrays"]
        idx = cls(m=meta["m"], ef_construction=meta["ef_construction"],
                  ef_search=meta["ef_search"],
                  ep_clusters=meta["ep_clusters"])
        idx.data = np.asarray(a["data"], np.float32)
        idx.node_level = np.asarray(a["node_level"])
        idx.layers = [np.asarray(a[f"layer_{li}"], np.int32)
                      for li in range(meta["n_layers"])]
        idx.entry = meta["entry"]
        # device tables only — the EP selector is restored verbatim, never
        # re-fit (k-means from a fresh key would break bit-identity)
        idx._device_tables()
        if "eps_centroids" in a:
            idx.eps = EntryPointSelector(
                centroids=jnp.asarray(a["eps_centroids"]),
                member_ids=jnp.asarray(a["eps_member_ids"]))
        return idx

    # -- search (device end to end) ----------------------------------------
    def entry_points(self, queries: jax.Array) -> jax.Array:
        """(Q, D) -> (Q,) int32 layer-0 entry ids, one device call."""
        q = jnp.asarray(queries, jnp.float32)
        if self.eps is not None:                 # paper §3.1 EP knob
            return self.eps.select(q)
        if self._upper.shape[0] == 0:            # single-layer graph
            return jnp.full((q.shape[0],), self.entry, jnp.int32)
        return _descend_upper(q, self._db, self._upper,
                              jnp.int32(self.entry))

    def search(self, queries: jax.Array, k: int, params=None, *,
               ef: Optional[int] = None, mode: Optional[str] = None):
        if params is not None:
            ef = ef if ef is not None else params.ef_search
            mode = mode if mode is not None else params.mode
        ef = ef or self.ef_s
        mode = mode or "while"
        q = jnp.asarray(queries, jnp.float32)
        entries = self.entry_points(q)
        d, i, _ = beam_search(q, self._db, self._nbr0, entries,
                              ef=max(ef, k), k=k, mode=mode)
        return d, i
