"""Jit'd public wrappers for the fused distance + top-k kernel.

Resolves interpret-vs-compiled from the backend on every call (like
``pdist/ops``) and picks tile sizes from the problem shape (m, n, d, k) with
the same lane-alignment rules as ``pdist``: 128-wide tiles (or the whole of
a narrower d), the int8 regime's query tile sublane-aligned to the int8
minimum (32).

``topk`` serves the f32 regimes (now including masked scans — the ``valid``
operand); ``topk_quant`` serves the int8 corpus-code regime fed by
``core/quant.QuantStore.device_view()``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels._compat import default_interpret
from repro.kernels.topk.topk import (
    CUBE_METRICS,
    MATMUL_METRICS,
    QUANT_METRICS,
    SUPPORTED,
    topk_pallas,
    topk_quant_pallas,
)

__all__ = [
    "topk", "topk_quant", "tile_config", "SUPPORTED", "MATMUL_METRICS",
    "CUBE_METRICS", "QUANT_METRICS",
]


def _round_up(x: int, mult: int) -> int:
    return x + (-x) % mult


def tile_config(m: int, n: int, d: int, k: int, metric: str,
                *, quantized: bool = False) -> dict:
    """(bm, bn, bk) for a (m, d) x (n, d) -> (m, k) scan.

    * bm: 128, shrunk (sublane-aligned) for small query batches so padding
      doesn't dominate — 8-aligned for f32 tiles, 32-aligned for the int8
      regime (the int8 minimum sublane tile).
    * bn: 128 by default; doubled for dataset-dominated MXU scans
      (n >= 64K) so the per-tile merge amortizes over more candidates.  The
      cube family keeps bn = 128 — widening it would blow the 2 MiB bound
      on the (bm, 32, bn) VPU intermediate.
    * bk: 128, or the whole (8-rounded) d when it is narrower — either way
      lane-aligned as Mosaic requires.  The VPU cube family reduces its
      d-block in 32-wide slices inside the kernel, which bounds the cube.
    """
    sub = 32 if quantized else 8
    bm = min(128, _round_up(max(m, 1), sub))
    bn = 256 if (n >= 65536 and metric not in CUBE_METRICS) else 128
    bk = min(128, _round_up(max(d, 1), 8))
    return dict(bm=bm, bn=bn, bk=bk)


def topk(
    X: jax.Array,
    Y: jax.Array,
    *,
    k: int,
    metric: str = "sqeuclidean",
    exclude_self: bool = False,
    valid: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    cfg = tile_config(X.shape[0], Y.shape[0], X.shape[1], k, metric)
    return topk_pallas(
        X, Y, k=k, metric=metric, exclude_self=exclude_self, valid=valid,
        interpret=default_interpret(), **cfg,
    )


def topk_quant(
    Q: jax.Array,
    codes: jax.Array,
    scales: jax.Array,
    *,
    k: int,
    metric: str = "euclidean",
    valid: jax.Array | None = None,
    sqnorms: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Int8 fused scan over corpus codes (first pass of a quantized engine).
    ``sqnorms`` — per-row squared dequant norms — is recomputed when the
    caller has no ``QuantStore.device_view()`` at hand."""
    if sqnorms is None:
        dec = codes.astype(jnp.float32) * scales[None, :]
        sqnorms = jnp.sum(dec * dec, axis=1)
    cfg = tile_config(
        Q.shape[0], codes.shape[0], Q.shape[1], k, metric, quantized=True
    )
    return topk_quant_pallas(
        Q, codes, scales, sqnorms, k=k, metric=metric, valid=valid,
        interpret=default_interpret(), **cfg,
    )
