"""Pallas TPU kernel: blocked semiring matmul over (min, combine).

This is the hot loop of the canonical q-metric projection (DESIGN.md §3.1):
one path-doubling sweep is ``M <- min(M, M (*) M)`` where

    (A (*) B)[i, j] = min_k combine(A[i, k], B[k, j])

with combine in {+, max, logaddexp}.  A (min, +) semiring product has no MXU
mapping (it is not a ring), so the kernel is VPU-bound by design; the tiling
goal is to keep the combine cube resident in VMEM and stream k tiles from
HBM exactly once per (i, j) output tile.

Tiling
------
grid = (m/bm, n/bn, k/bk), k innermost ("arbitrary" semantics) so the output
tile acts as the running-min accumulator across k steps.  Default tile
(bm, bn, bk) = (128, 128, 128): every A/B block is lane-aligned (last dim a
multiple of 128, which Mosaic requires).  Inside a step the k-block is
reduced in ``SUB``-wide slices, so the combine cube is
(bm, SUB, bn) = 128*8*128*4B = 512 KiB rather than the 8 MiB a whole
(bm, bk, bn) cube would take — tiles + cube + accumulator fit the ~16 MiB
of VMEM alongside double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import default_interpret

#: k-slice width of the (bm, SUB, bn) combine cube; ``bk`` is a multiple of it
SUB = 8


def _combine(a, b, mode: str):
    if mode == "minplus":
        return a + b
    if mode == "minmax":
        return jnp.maximum(a, b)
    if mode == "logminplus":
        return jnp.logaddexp(a, b)
    raise ValueError(mode)


def _qpath_kernel(a_ref, b_ref, o_ref, *, mode: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)

    acc = o_ref[...]  # (bm, bn)
    for c in range(0, a_ref.shape[1], SUB):
        a = a_ref[:, c : c + SUB]  # (bm, SUB)
        b = b_ref[c : c + SUB, :]  # (SUB, bn)
        cube = _combine(a[:, :, None], b[None, :, :], mode)  # (bm, SUB, bn)
        acc = jnp.minimum(acc, jnp.min(cube, axis=1))
    o_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("mode", "bm", "bn", "bk", "interpret")
)
def qpath_matmul_pallas(
    A: jax.Array,
    B: jax.Array,
    *,
    mode: str = "minmax",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Semiring matmul via pallas_call.  Shapes padded to tile multiples with
    +inf (the min identity), so arbitrary (m, k) x (k, n) are supported.
    ``bk`` must be a multiple of ``SUB``."""
    if interpret is None:
        interpret = default_interpret()
    m, kdim = A.shape
    k2, n = B.shape
    assert kdim == k2, (A.shape, B.shape)
    dtype = jnp.float32
    A = A.astype(dtype)
    B = B.astype(dtype)

    pm, pk, pn = (-m) % bm, (-kdim) % bk, (-n) % bn
    Ap = jnp.pad(A, ((0, pm), (0, pk)), constant_values=jnp.inf)
    Bp = jnp.pad(B, ((0, pk), (0, pn)), constant_values=jnp.inf)
    M, K, N = Ap.shape[0], Ap.shape[1], Bp.shape[1]
    grid = (M // bm, N // bn, K // bk)

    out = pl.pallas_call(
        functools.partial(_qpath_kernel, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(Ap, Bp)
    return out[:m, :n]
