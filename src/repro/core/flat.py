"""Brute-force (FlatL2) index — the paper's baseline and the recall oracle."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.distances import l2_topk
from repro.serve.spans import span


@dataclass
class FlatIndex:
    """Exact index; conforms to the ``core.index_api.Index`` protocol.

    ``FlatIndex(data)`` and ``FlatIndex().fit(data)`` are equivalent.
    """
    data: Optional[jax.Array] = None

    def fit(self, data: jax.Array, *, key: Optional[jax.Array] = None):
        self.data = data
        return self

    @property
    def ntotal(self) -> int:
        return 0 if self.data is None else self.data.shape[0]

    @property
    def dim(self) -> int:
        return 0 if self.data is None else self.data.shape[1]

    def search(self, queries: jax.Array, k: int, params=None, *,
               chunk: Optional[int] = None):
        """Exact (dists, ids); the oracle every other index is scored against.

        An explicit ``chunk=`` keyword wins over ``params.chunk`` (same
        precedence as the other families' ``ef=``/``mode=`` overrides).
        """
        if chunk is None and params is not None:
            chunk = params.chunk
        with span("index.search"), span("search.scan"):
            return l2_topk(queries, self.data, k, chunk=chunk or 16384)

    def search_params_space(self):
        # exact search always has recall 1.0; chunk is its one (QPS-only)
        # runtime knob, tunable through the generic path like any other
        from repro.core.tuning.space import Int, SearchSpace
        return SearchSpace().add("chunk", Int(1024, 65536, log=True))

    def memory_bytes(self) -> int:
        return int(self.data.size * self.data.dtype.itemsize)

    # -- persistence (core/persist.py) ------------------------------------
    def state_dict(self) -> dict:
        return {"meta": {}, "arrays": {"data": self.data}}

    @classmethod
    def from_state(cls, state: dict) -> "FlatIndex":
        idx = cls()
        idx.data = jnp.asarray(state["arrays"]["data"])
        return idx


def recall_at_k(pred_ids: jax.Array, true_ids: jax.Array) -> float:
    """Paper's Recall@k = |R ∩ R_hat| / k, averaged over queries.

    k is the number of *requested* neighbors (pred columns). The oracle may
    supply more columns than k (they are distance-ascending): only its first
    k count as R, so a wider oracle inflates neither numerator nor
    denominator.
    """
    k = pred_ids.shape[1]
    hits = (pred_ids[:, :, None] == true_ids[:, None, :k]).any(-1)
    valid = pred_ids >= 0
    return float(jnp.mean(jnp.sum(hits & valid, axis=1) / k))
