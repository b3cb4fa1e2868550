"""Dissimilarity functions (paper §2, Table 1).

Every metric has two entry points:
  * ``<name>(x, y)``            — single-pair dissimilarity, jnp scalars in/out.
  * ``<name>_matrix(X, Y)``     — blocked (m, n) pairwise matrix.

All are pure jnp and jit/vmap friendly. ``pairwise`` dispatches by name and is
the single integration point used by the projection, VP tree, baselines and
benchmarks. The Pallas ``kernels/pdist`` path is selected by
``pairwise(..., impl="pallas")`` where the metric is supported.

Naming note: this module is the DISSIMILARITY registry.  Operational
metrics — counters, latency histograms, Prometheus exposition — live in
``repro.core.telemetry`` (DESIGN.md §16), which is never re-exported under
the name ``metrics``; keep the two namespaces apart (``__all__`` below is
the explicit public surface of this one).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = [
    "EPS", "METRICS",
    "euclidean", "sqeuclidean", "manhattan", "chebyshev", "cosine",
    "correlation", "jaccard", "dot",
    "euclidean_matrix", "sqeuclidean_matrix", "manhattan_matrix",
    "chebyshev_matrix", "cosine_matrix", "correlation_matrix",
    "jaccard_matrix", "dot_matrix",
    "pair_fn", "matrix_fn", "pairwise",
]

EPS = 1e-12

# ---------------------------------------------------------------------------
# Single-pair forms
# ---------------------------------------------------------------------------


def euclidean(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.maximum(jnp.sum((x - y) ** 2), 0.0))


def sqeuclidean(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.sum((x - y) ** 2)


def manhattan(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.sum(jnp.abs(x - y))


def chebyshev(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(x - y))


def cosine(x: jax.Array, y: jax.Array) -> jax.Array:
    nx = jnp.sqrt(jnp.sum(x * x))
    ny = jnp.sqrt(jnp.sum(y * y))
    return 1.0 - jnp.dot(x, y) / jnp.maximum(nx * ny, EPS)


def correlation(x: jax.Array, y: jax.Array) -> jax.Array:
    xc = x - jnp.mean(x)
    yc = y - jnp.mean(y)
    return cosine(xc, yc)


def jaccard(x: jax.Array, y: jax.Array) -> jax.Array:
    """Jaccard dissimilarity for binary (0/1) vectors."""
    xb = x > 0
    yb = y > 0
    inter = jnp.sum(jnp.logical_and(xb, yb))
    union = jnp.sum(jnp.logical_or(xb, yb))
    return 1.0 - inter / jnp.maximum(union, 1)


def dot(x: jax.Array, y: jax.Array) -> jax.Array:
    """Negative inner product (maximum-inner-product search as dissimilarity)."""
    return -jnp.dot(x, y)


# ---------------------------------------------------------------------------
# Matrix forms — MXU-friendly formulations where possible
# ---------------------------------------------------------------------------

#: the precision of every f32 distance matmul, here and in the Pallas
#: kernels: on a TPU the default f32 matmul is a single bf16 pass (~3
#: significant digits), which would reorder near neighbours in every exact
#: scan.  Other backends ignore it.
F32_PRECISION = jax.lax.Precision.HIGHEST


def sqeuclidean_matrix(X: jax.Array, Y: jax.Array) -> jax.Array:
    """(m, n) squared distances via ``|x|^2 + |y|^2 - 2 x.yT`` (one matmul)."""
    x2 = jnp.sum(X * X, axis=-1)[:, None]
    y2 = jnp.sum(Y * Y, axis=-1)[None, :]
    d2 = x2 + y2 - 2.0 * jnp.dot(X, Y.T, precision=F32_PRECISION)
    return jnp.maximum(d2, 0.0)


def euclidean_matrix(X: jax.Array, Y: jax.Array) -> jax.Array:
    return jnp.sqrt(sqeuclidean_matrix(X, Y))


def manhattan_matrix(X: jax.Array, Y: jax.Array) -> jax.Array:
    # O(m n d) with broadcast; blocked by the caller for large m,n.
    return jnp.sum(jnp.abs(X[:, None, :] - Y[None, :, :]), axis=-1)


def chebyshev_matrix(X: jax.Array, Y: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(X[:, None, :] - Y[None, :, :]), axis=-1)


def cosine_matrix(X: jax.Array, Y: jax.Array) -> jax.Array:
    Xn = X / jnp.maximum(jnp.linalg.norm(X, axis=-1, keepdims=True), EPS)
    Yn = Y / jnp.maximum(jnp.linalg.norm(Y, axis=-1, keepdims=True), EPS)
    return 1.0 - jnp.dot(Xn, Yn.T, precision=F32_PRECISION)


def correlation_matrix(X: jax.Array, Y: jax.Array) -> jax.Array:
    Xc = X - jnp.mean(X, axis=-1, keepdims=True)
    Yc = Y - jnp.mean(Y, axis=-1, keepdims=True)
    return cosine_matrix(Xc, Yc)


def jaccard_matrix(X: jax.Array, Y: jax.Array) -> jax.Array:
    Xb = (X > 0).astype(jnp.float32)
    Yb = (Y > 0).astype(jnp.float32)
    inter = Xb @ Yb.T  # MXU-friendly
    sx = jnp.sum(Xb, axis=-1)[:, None]
    sy = jnp.sum(Yb, axis=-1)[None, :]
    union = sx + sy - inter
    return 1.0 - inter / jnp.maximum(union, 1.0)


def dot_matrix(X: jax.Array, Y: jax.Array) -> jax.Array:
    return -jnp.dot(X, Y.T, precision=F32_PRECISION)


_PAIR: dict[str, Callable] = {
    "euclidean": euclidean,
    "sqeuclidean": sqeuclidean,
    "manhattan": manhattan,
    "chebyshev": chebyshev,
    "cosine": cosine,
    "correlation": correlation,
    "jaccard": jaccard,
    "dot": dot,
}

_MATRIX: dict[str, Callable] = {
    "euclidean": euclidean_matrix,
    "sqeuclidean": sqeuclidean_matrix,
    "manhattan": manhattan_matrix,
    "chebyshev": chebyshev_matrix,
    "cosine": cosine_matrix,
    "correlation": correlation_matrix,
    "jaccard": jaccard_matrix,
    "dot": dot_matrix,
}

METRICS = tuple(sorted(_PAIR))


def pair_fn(metric: str) -> Callable:
    if metric not in _PAIR:
        raise KeyError(f"unknown metric {metric!r}; available: {METRICS}")
    return _PAIR[metric]


def matrix_fn(metric: str) -> Callable:
    if metric not in _MATRIX:
        raise KeyError(f"unknown metric {metric!r}; available: {METRICS}")
    return _MATRIX[metric]


@functools.partial(jax.jit, static_argnames=("metric", "block", "impl"))
def pairwise(
    X: jax.Array,
    Y: jax.Array,
    *,
    metric: str = "euclidean",
    block: int = 0,
    impl: str = "jnp",
) -> jax.Array:
    """Pairwise dissimilarity matrix.

    ``block > 0`` evaluates the matrix in row blocks of that size via
    ``lax.map`` to bound peak memory for the O(mnd) metrics (manhattan /
    chebyshev); the matmul-based metrics don't need it.
    ``impl='pallas'`` routes supported metrics through ``kernels/pdist``.
    """
    if impl == "pallas":
        from repro.kernels.pdist import ops as pdist_ops

        if metric in pdist_ops.SUPPORTED:
            return pdist_ops.pdist(X, Y, metric=metric)
        # kernel-unsupported metrics (jaccard, correlation) fall back to jnp
    fn = _MATRIX[metric]
    if block and X.shape[0] > block:
        m = X.shape[0]
        pad = (-m) % block
        Xp = jnp.pad(X, ((0, pad), (0, 0)))
        blocks = Xp.reshape(-1, block, X.shape[1])
        out = jax.lax.map(lambda xb: fn(xb, Y), blocks)
        return out.reshape(-1, Y.shape[0])[:m]
    return fn(X, Y)
