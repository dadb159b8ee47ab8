"""Vector codecs for the quantized traversal hot path (VSAG-style).

The beam-search inner loop is memory-bandwidth-bound on the f32 vector
table: every hop gathers R rows of D*4 bytes. A ``Codec`` replaces those
rows with compact uint8 codes plus a small per-query *lookup table* (LUT)
so one hop reads R rows of M bytes instead — the asymmetric-distance
formulation every production quantized-graph system (VSAG, ScaNN, faiss
HNSW-PQ) traverses with, finished by an exact f32 rerank of the few beam
survivors.

Both codecs expose the SAME serving contract so a single LUT-accumulation
kernel (``kernels/lut_dist``) serves either:

  * ``encode(data)``  -> (N, M) uint8 codes;
  * ``lut(queries)``  -> (Q, M, C) f32 per-query sub-distance tables;
  * approx sq-distance(q, n) = sum_m lut[q, m, codes[n, m]].

``PQCodec`` is classic product quantization: M sub-spaces x C centroids
trained with the repo's k-means (the codebooks ``core/pq.py`` now
delegates to). ``Int8Codec`` is scalar quantization: per-dim scale and
zero-point, codes symmetric around the zero-point — its LUT is the
dsub=1, uniform-grid degenerate case of PQ's (M = D), which is exactly
what lets both share the kernel. On MXU hardware the int8 codes also
admit 8-bit matmul tiles; the LUT form is the portable contract.
"""
from __future__ import annotations

import functools
from typing import Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.distances import l2_topk
from repro.core.kmeans import kmeans_blocks


@runtime_checkable
class Codec(Protocol):
    """Structural interface of a traversal codec."""

    def fit(self, data: jax.Array, *, key: Optional[jax.Array] = None):
        """Train on (N, D) vectors; returns self."""
        ...

    def encode(self, data: jax.Array) -> jax.Array:
        """(N, D) f32 -> (N, M) uint8 codes."""
        ...

    def decode(self, codes: jax.Array) -> jax.Array:
        """(N, M) uint8 -> (N, D) f32 reconstruction."""
        ...

    def lut(self, queries: jax.Array) -> jax.Array:
        """(Q, D) f32 -> (Q, M, C) f32 per-query sub-distance tables."""
        ...

    def memory_bytes(self) -> int:
        """Codebook/scale footprint (codes are accounted by their owner)."""
        ...

    @property
    def code_bytes(self) -> int:
        """Bytes per encoded vector (M) — the hot-path row width."""
        ...


def default_pq_m(dim: int) -> int:
    """Largest divisor of ``dim`` no bigger than dim // 2 (2-dim+ subspaces).

    The ``pq_m=0`` auto rule: dim=96 -> 48 (the paper-scale ``PQ48x8``),
    dim=32 -> 16. Falls back to 1 (one whole-vector quantizer) for primes.
    """
    for m in range(dim // 2, 0, -1):
        if dim % m == 0:
            return m
    return 1


# --------------------------------------------------------------------------
# shared jitted arithmetic (core/pq.py delegates here — ONE implementation)
# --------------------------------------------------------------------------


@jax.jit
def pq_lut(queries: jax.Array, codebooks: jax.Array) -> jax.Array:
    """(Q, D) queries x (M, C, dsub) codebooks -> (Q, M, C) sq-dist LUT.

    The asymmetric-distance table: entry [q, m, c] is the squared L2
    between query q's m-th sub-vector and centroid c of sub-space m.
    """
    qn = queries.shape[0]
    m, c, dsub = codebooks.shape
    qsub = queries.reshape(qn, m, dsub).astype(jnp.float32)
    diff = qsub[:, :, None, :] - codebooks[None].astype(jnp.float32)
    return jnp.sum(diff * diff, axis=-1)


@jax.jit
def pq_decode(codes: jax.Array, codebooks: jax.Array) -> jax.Array:
    """(N, M) codes x (M, C, dsub) codebooks -> (N, M*dsub) reconstruction."""
    n, m = codes.shape
    rows = codebooks[jnp.arange(m)[None, :], codes.astype(jnp.int32)]
    return rows.reshape(n, -1)


class PQCodec:
    """Product quantizer: M sub-spaces, C<=256 k-means centroids each.

    Training runs ``core.kmeans`` on every sub-space (key ``fold_in(key,
    j)`` for sub-space j) in one program (``kmeans_blocks``). The
    standalone PQ baseline (``core/pq.py``) delegates here, so the
    codebooks and codes are the same.
    """

    def __init__(self, m: int, n_centroids: int = 256):
        if m < 1:
            raise ValueError(f"pq m={m} must be >= 1")
        self.m = m
        self.n_centroids = n_centroids
        self.codebooks: Optional[jax.Array] = None   # (M, C, dsub)
        self.codes: Optional[jax.Array] = None       # (N, M) uint8 train codes

    def fit(self, data: jax.Array, *, key: Optional[jax.Array] = None,
            iters: int = 8):
        key = key if key is not None else jax.random.PRNGKey(0)
        n, d = data.shape
        if d % self.m != 0:
            raise ValueError(
                f"PQ m={self.m} does not divide dim={d}; pick m from the "
                f"divisors of the (post-PCA) dimensionality")
        # sub-space j is kmeans(fold_in(key, j), its columns), all M in one
        # program; its final assignments are encode(data)'s column j, by the
        # same nearest-centroid arithmetic
        km = kmeans_blocks(key, data, self.m, min(self.n_centroids, n),
                           iters=iters)
        self.codebooks = km.centroids
        self.codes = km.assignments.T.astype(jnp.uint8)
        return self

    def encode(self, data: jax.Array) -> jax.Array:
        n, d = data.shape
        sub = data.reshape(n, self.m, d // self.m)
        cols = []
        for j in range(self.m):
            # same nearest-centroid arithmetic k-means assigns with, so
            # encode(train_data) == the k-means assignments bit-for-bit
            _, ids = l2_topk(sub[:, j], self.codebooks[j], 1)
            cols.append(ids[:, 0].astype(jnp.uint8))
        return jnp.stack(cols, axis=1)

    def decode(self, codes: jax.Array) -> jax.Array:
        return pq_decode(codes, self.codebooks)

    def lut(self, queries: jax.Array) -> jax.Array:
        return pq_lut(queries, self.codebooks)

    def memory_bytes(self) -> int:
        return int(self.codebooks.size * 4)

    @property
    def code_bytes(self) -> int:
        return self.m


# --------------------------------------------------------------------------
# scalar int8
# --------------------------------------------------------------------------

_SQ8_LEVELS = 254          # codes occupy [-127, 127] around the zero-point
_SQ8_ZERO_CODE = 127       # uint8 storage offset: stored = signed + 127


@jax.jit
def _sq8_encode(data, scale, zero):
    q = jnp.round((data.astype(jnp.float32) - zero) / scale)
    q = jnp.clip(q, -_SQ8_ZERO_CODE, _SQ8_ZERO_CODE)
    return (q + _SQ8_ZERO_CODE).astype(jnp.uint8)


@jax.jit
def _sq8_lut(queries, scale, zero):
    # grid[d, v] = dequant(v, d): the 256 reconstruction levels per dim
    # (entry 255 is out of the symmetric range but kept for a pow2 C)
    levels = (jnp.arange(256, dtype=jnp.float32)
              - _SQ8_ZERO_CODE)                       # (256,)
    grid = zero[:, None] + scale[:, None] * levels[None, :]   # (D, 256)
    diff = queries.astype(jnp.float32)[:, :, None] - grid[None]
    return diff * diff                                # (Q, D, 256)


class Int8Codec:
    """Per-dim scalar quantizer: symmetric int8 codes around a zero-point.

    code = clip(round((x - zero_d) / scale_d), -127, 127), stored as
    uint8 (+127). The LUT view treats every dim as a 256-level
    sub-quantizer (dsub=1 PQ on a uniform grid), so the same
    ``kernels/lut_dist`` accumulation serves SQ8 and PQ traversal. 4x
    smaller rows than f32 with no codebook training.
    """

    def __init__(self):
        self.scale: Optional[jax.Array] = None   # (D,) f32
        self.zero: Optional[jax.Array] = None    # (D,) f32 zero-point

    def fit(self, data: jax.Array, *, key: Optional[jax.Array] = None):
        del key                                   # deterministic fit
        lo = jnp.min(data.astype(jnp.float32), axis=0)
        hi = jnp.max(data.astype(jnp.float32), axis=0)
        self.zero = (lo + hi) * 0.5
        self.scale = jnp.maximum((hi - lo) / _SQ8_LEVELS, 1e-12)
        return self

    def encode(self, data: jax.Array) -> jax.Array:
        return _sq8_encode(data, self.scale, self.zero)

    def decode(self, codes: jax.Array) -> jax.Array:
        signed = codes.astype(jnp.float32) - _SQ8_ZERO_CODE
        return self.zero[None] + signed * self.scale[None]

    def lut(self, queries: jax.Array) -> jax.Array:
        return _sq8_lut(queries, self.scale, self.zero)

    def memory_bytes(self) -> int:
        return int((self.scale.size + self.zero.size) * 4)

    @property
    def code_bytes(self) -> int:
        return int(self.scale.shape[0])


def make_codec(dist_backend: str, dim: int, pq_m: int = 0,
               n_centroids: int = 256):
    """Codec for a ``dist_backend`` name ("pq" | "int8"); pq_m=0 -> auto."""
    if dist_backend == "pq":
        return PQCodec(pq_m or default_pq_m(dim), n_centroids)
    if dist_backend == "int8":
        return Int8Codec()
    raise ValueError(
        f"unknown dist_backend {dist_backend!r} (expected 'pq' | 'int8'; "
        f"'f32' means unquantized traversal, which needs no codec)")
