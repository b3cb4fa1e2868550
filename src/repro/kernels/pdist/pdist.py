"""Pallas TPU kernel: tiled pairwise distance matrix (query x dataset).

Feeds the kNN-graph build, IVF scoring and the two-stage rerank.  Two kernel
regimes (DESIGN.md §4):

* matmul family (sqeuclidean / euclidean / cosine / dot):
  ``|x|^2 + |y|^2 - 2 x.yT`` — the cross term runs on the MXU; squared norms
  accumulate in f32 VMEM scratch across d-tiles; the epilogue (norm add,
  clamp, sqrt / cosine normalize) is fused into the final k-step so the
  distance matrix is written to HBM exactly once.
* elementwise family (manhattan / chebyshev):
  |x - y| cube reduced on the VPU, accumulated directly into the output tile
  across k-steps.

grid = (m/bm, n/bn, d/bk), k innermost.  Defaults (128, 128, 128) keep every
tile lane-aligned, which Mosaic requires of the d-block for both families.
The cube family reduces its d-block in ``CUBE_SUB``-wide slices
(``cube_accumulate``), which bounds the VMEM cube at
128*32*128*4B = 2 MiB whatever the d-block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.metrics import F32_PRECISION
from repro.kernels._compat import default_interpret

EPS = 1e-12
_MATMUL = ("sqeuclidean", "euclidean", "cosine", "dot")
_CUBE = ("manhattan", "chebyshev")
#: d-slice width of the elementwise family's (bm, CUBE_SUB, bn) VMEM cube
CUBE_SUB = 32


def cube_accumulate(acc, x, y, *, metric: str):
    """Fold one (bm, bk) x (bn, bk) d-block into the (bm, bn) manhattan /
    chebyshev accumulator, ``CUBE_SUB`` lanes of d at a time so the
    (bm, CUBE_SUB, bn) |x - y| cube stays bounded (shared with
    ``kernels/topk``)."""
    yt = y.T  # (bk, bn)
    for c in range(0, x.shape[1], CUBE_SUB):
        xs = x[:, c : c + CUBE_SUB]  # (bm, CUBE_SUB)
        ys = yt[c : c + CUBE_SUB, :]  # (CUBE_SUB, bn)
        cube = jnp.abs(xs[:, :, None] - ys[None, :, :])  # (bm, CUBE_SUB, bn)
        if metric == "manhattan":
            acc = acc + jnp.sum(cube, axis=1)
        else:  # chebyshev
            acc = jnp.maximum(acc, jnp.max(cube, axis=1))
    return acc


def _matmul_kernel(x_ref, y_ref, o_ref, acc, sx, sy, *, metric: str, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        sx[...] = jnp.zeros_like(sx)
        sy[...] = jnp.zeros_like(sy)

    x = x_ref[...].astype(jnp.float32)  # (bm, bk)
    y = y_ref[...].astype(jnp.float32)  # (bn, bk)
    acc[...] += jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=F32_PRECISION,
    )
    sx[...] += jnp.sum(x * x, axis=1, keepdims=True)
    sy[...] += jnp.sum(y * y, axis=1, keepdims=True)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        dotv = acc[...]
        if metric == "dot":
            o_ref[...] = -dotv
        elif metric == "cosine":
            nx = jnp.sqrt(jnp.maximum(sx[...], EPS))  # (bm, 1)
            ny = jnp.sqrt(jnp.maximum(sy[...], EPS))  # (bn, 1)
            o_ref[...] = 1.0 - dotv / (nx * ny.T)
        else:
            d2 = jnp.maximum(sx[...] + sy[...].T - 2.0 * dotv, 0.0)
            o_ref[...] = jnp.sqrt(d2) if metric == "euclidean" else d2


def _cube_kernel(x_ref, y_ref, o_ref, *, metric: str, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] = cube_accumulate(
        o_ref[...], x_ref[...].astype(jnp.float32),
        y_ref[...].astype(jnp.float32), metric=metric,
    )


@functools.partial(
    jax.jit, static_argnames=("metric", "bm", "bn", "bk", "interpret")
)
def pdist_pallas(
    X: jax.Array,
    Y: jax.Array,
    *,
    metric: str = "sqeuclidean",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    # benchmarks call the kernel directly (not via ops.pdist): resolve the
    # interpret default from the backend so TPU runs compiled by default.
    if interpret is None:
        interpret = default_interpret()
    m, d = X.shape
    n, d2 = Y.shape
    assert d == d2, (X.shape, Y.shape)

    pm, pn, pk = (-m) % bm, (-n) % bn, (-d) % bk
    # zero padding in d is exact for every supported metric; padded rows are
    # sliced off after the call.
    Xp = jnp.pad(X, ((0, pm), (0, pk)))
    Yp = jnp.pad(Y, ((0, pn), (0, pk)))
    M, N, K = Xp.shape[0], Yp.shape[0], Xp.shape[1]
    grid = (M // bm, N // bn, K // bk)

    common = dict(
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )
    if metric in _MATMUL:
        out = pl.pallas_call(
            functools.partial(_matmul_kernel, metric=metric, k_steps=grid[2]),
            scratch_shapes=[
                pltpu.VMEM((bm, bn), jnp.float32),
                pltpu.VMEM((bm, 1), jnp.float32),
                pltpu.VMEM((bn, 1), jnp.float32),
            ],
            **common,
        )(Xp, Yp)
    elif metric in _CUBE:
        out = pl.pallas_call(
            functools.partial(_cube_kernel, metric=metric, k_steps=grid[2]),
            **common,
        )(Xp, Yp)
    else:
        raise ValueError(f"pdist kernel does not support metric {metric!r}")
    return out[:m, :n]
