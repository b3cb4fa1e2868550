"""Jit'd public wrapper for the pairwise-distance kernel."""
from __future__ import annotations

import jax

from repro.kernels._compat import default_interpret
from repro.kernels.pdist.pdist import pdist_pallas

SUPPORTED = ("sqeuclidean", "euclidean", "cosine", "dot", "manhattan", "chebyshev")


def pdist(X: jax.Array, Y: jax.Array, *, metric: str = "sqeuclidean") -> jax.Array:
    return pdist_pallas(X, Y, metric=metric, interpret=default_interpret())
