"""PCA dimensionality reduction (paper §3.1, knob D).

Fit via eigendecomposition of the covariance matrix (D0 x D0 — cheap even for
D0=768 regardless of N); transform is a single matmul, which is exactly why
the paper uses it: it shrinks the L2 hotspot's inner dimension.
"""
from __future__ import annotations

from dataclasses import dataclass
import functools

import jax
import jax.numpy as jnp

from repro.core.distances import EXACT


@dataclass(frozen=True)
class PCA:
    mean: jax.Array          # (D0,)
    components: jax.Array    # (D0, D) top-D eigvecs, column-major
    explained: jax.Array     # (D,) explained-variance ratios (descending)

    @property
    def dim(self) -> int:
        return self.components.shape[1]

    def transform(self, x: jax.Array) -> jax.Array:
        return jnp.matmul(x - self.mean, self.components, precision=EXACT)

    def inverse_transform(self, z: jax.Array) -> jax.Array:
        return jnp.matmul(z, self.components.T, precision=EXACT) + self.mean


@functools.partial(jax.jit, static_argnames=("dim",))
def _fit(x: jax.Array, dim: int):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=0)
    xc = x32 - mean
    cov = jnp.matmul(xc.T, xc, precision=EXACT) / (x.shape[0] - 1)
    evals, evecs = jnp.linalg.eigh(cov)          # ascending
    evals = evals[::-1]
    evecs = evecs[:, ::-1]
    total = jnp.maximum(jnp.sum(evals), 1e-12)
    return mean, evecs[:, :dim], evals[:dim] / total


def fit_pca(x: jax.Array, dim: int) -> PCA:
    if not 1 <= dim <= x.shape[1]:
        raise ValueError(f"pca dim {dim} out of range (1, {x.shape[1]})")
    mean, comps, ratio = _fit(x, dim)
    return PCA(mean=mean, components=comps, explained=ratio)


def dim_for_energy(x: jax.Array, energy: float) -> int:
    """Smallest D capturing ``energy`` fraction of variance (tuner helper)."""
    full = fit_pca(x, x.shape[1])
    cum = jnp.cumsum(full.explained)
    return int(jnp.searchsorted(cum, energy) + 1)
