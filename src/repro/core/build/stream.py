"""Out-of-core build substrate: fixed-size chunk streaming + host offload.

Two pieces, both deliberately tiny, that the rest of the build stack
composes out of:

  * **chunk spans** — every O(N * R) pass in the build (sorted
    adjacencies, reprune derivations, candidate-pool assembly) is
    row-independent, so it can stream over ``chunk_spans(n, chunk)`` and
    never materialize the per-structure ``(N, R)`` f32 distance table:
    the float peak is ``(chunk, R)``, the only N-proportional arrays left
    are the int32 products the caller needs anyway (the adjacency
    itself). ``ANN_BUILD_CHUNK`` overrides the default chunk globally —
    the knob that bounds device temp memory for >HBM builds.

  * **``HostOffloadStore``** — the chunked host-offload tier: keyed
    pytrees of arrays parked in host buffers (pinned-host device memory
    when the accelerator exposes a ``pinned_host`` memory space, plain
    numpy on CPU), with one-deep *prefetch*: ``prefetch(key)`` starts the
    async ``device_put`` of the NEXT chunk while the CURRENT chunk's
    device work is still dispatched, so on an async backend the H2D
    transfer overlaps compute. ``fetch(key)`` consumes the staged copy
    (or transfers on the spot). This is what lets one box build and
    serve shard sets whose total footprint exceeds HBM: only the active
    shard (plus the prefetched next one) is device-resident.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import numpy as np

DEFAULT_CHUNK = int(os.environ.get("ANN_BUILD_CHUNK", 2048))


def chunk_spans(n: int, chunk: Optional[int] = None
                ) -> Iterator[Tuple[int, int]]:
    """Fixed-size (start, end) row spans covering [0, n)."""
    chunk = chunk or DEFAULT_CHUNK
    for s in range(0, n, chunk):
        yield s, min(s + chunk, n)


def pinned_host_sharding():
    """A pinned-host placement target, or None on CPU (where arrays are
    host-resident anyway) and on backends without that memory space."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    if "pinned_host" not in {m.kind for m in dev.addressable_memories()}:
        return None
    return jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")


def _to_host(x):
    """One array -> host buffer (pinned device memory when available)."""
    pin = pinned_host_sharding()
    if pin is not None:
        return jax.device_put(x, pin)
    return np.asarray(x)


def _to_device(x):
    """One host buffer -> the default device's own memory."""
    return jax.device_put(x, jax.devices()[0])


class HostOffloadStore:
    """Keyed host-resident array pytrees with one-deep device prefetch.

    ``offload(key, tree)`` copies every leaf to a host buffer (the caller
    drops its device references afterwards — that is what frees HBM);
    ``prefetch(key)`` stages the async H2D transfer of a whole tree;
    ``fetch(key)`` returns the device tree, consuming the staged copy if
    one exists. The staging dict is intentionally one-deep per key: the
    double-buffer discipline (prefetch ``i+1`` while computing on ``i``)
    bounds device residency at two chunks, which is the entire point.
    """

    def __init__(self):
        self._host: Dict[Any, Any] = {}
        self._staged: Dict[Any, Any] = {}

    def __contains__(self, key) -> bool:
        return key in self._host

    def keys(self):
        return self._host.keys()

    def offload(self, key, tree) -> None:
        """Copy a pytree of arrays to host buffers under ``key``."""
        self._host[key] = jax.tree.map(_to_host, tree)
        self._staged.pop(key, None)     # stale device copy, if any

    def prefetch(self, key) -> None:
        """Start the async device transfer of ``key``'s tree (no-op when
        unknown or already staged)."""
        if key in self._host and key not in self._staged:
            self._staged[key] = jax.tree.map(_to_device, self._host[key])

    def fetch(self, key):
        """Device-resident tree for ``key`` (consumes the staged copy)."""
        tree = self._staged.pop(key, None)
        if tree is None:
            tree = jax.tree.map(_to_device, self._host[key])
        return tree

    def peek_host(self, key):
        """The raw host tree (zero-copy on CPU; for size accounting and
        chunked re-uploads)."""
        return self._host[key]

    def drop(self, key) -> None:
        self._host.pop(key, None)
        self._staged.pop(key, None)

    def nbytes(self) -> int:
        total = 0
        for tree in self._host.values():
            for leaf in jax.tree.leaves(tree):
                total += int(np.asarray(leaf).nbytes) if not hasattr(
                    leaf, "nbytes") else int(leaf.nbytes)
        return total
