"""Pallas TPU kernel: fused pairwise distance + streaming top-k (DESIGN.md §4.3).

Every scan path in the pipeline (kNN-graph build, brute-force ground truth,
IVF scoring, two-stage rerank) is "compute an (m, n) distance matrix, select
k" — but only (m, k) of the result survives.  Materializing the matrix costs
O(m·n) HBM writes + reads and an O(m·n·log n) host-side selection.  This
kernel never materializes it: the distance tile lives in VMEM, a running
(k-wide value, index) top-k accumulator lives in VMEM scratch across n-tiles,
and only the final (m, k) result is ever written to HBM.

Structure (grid = (m/bm, n/bn, d/bk), d innermost, n-then-d "arbitrary"):

* distance tile — same two regimes as ``kernels/pdist``: the matmul family
  (sqeuclidean / euclidean / cosine / dot) accumulates the MXU cross term +
  squared norms in f32 scratch across d-tiles; the elementwise family
  (manhattan / chebyshev) reduces the |x-y| cube on the VPU, in d-slices
  (``pdist.cube_accumulate``) so the cube stays at 2 MiB.
* streaming selection — at the last d-step the finished (bm, bn) tile is
  merged into the (bm, k) running top-k by k rounds of masked min-extraction
  over the (bm, k + bn) concatenation (a partition merge: each round peels
  the row minimum and poisons it with +inf).  Ties resolve to the lowest
  dataset index, matching ``jax.lax.top_k`` on the negated matrix.
* tile skipping — a tile whose global minimum is no better than every row's
  current k-th distance cannot change the accumulator; the merge is wrapped
  in ``pl.when`` so converged rows stream past most of the dataset at pure
  distance-compute cost.

Self-exclusion (kNN graphs: X scanned against itself) is an index mask
``global_row == global_col`` applied to the tile before the merge, so no
(n, n) eye matrix is ever built.

Two extensions (DESIGN.md §13):

* per-candidate ``valid`` mask — an optional (1, n) 0/1 operand tiled
  (1, bn) alongside the dataset; masked columns are +inf'd in the epilogue
  before the merge, so irregular candidate sets (IVF padded lists, filter
  predicates, live delta slots) run the fused kernel instead of falling
  back to the blocked jnp path.  An all-masked tile simply fails the
  can-improve bound and streams past at pure distance-compute cost.
* int8 regime (``topk_quant_pallas``) — the corpus arrives as per-dimension
  absmax codes (``core/quant``): the cross term is an int8 x int8 MXU
  matmul accumulated in int32 scratch (the query is folded against the
  corpus scales and row-quantized outside the kernel), and the epilogue
  dequantizes in f32 scratch: ``d2 = |q|^2 + |dec(c)|^2 - 2*alpha*acc``
  with both norm vectors precomputed operands.  HBM reads 1 byte/dim of
  corpus instead of 4 — the memory-bandwidth win the quantized engines
  are built on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant as quant_lib
from repro.core.metrics import F32_PRECISION
from repro.kernels._compat import default_interpret
from repro.kernels.pdist.pdist import cube_accumulate

EPS = 1e-12
MATMUL_METRICS = ("sqeuclidean", "euclidean", "cosine", "dot")
CUBE_METRICS = ("manhattan", "chebyshev")
SUPPORTED = MATMUL_METRICS + CUBE_METRICS
#: metrics the int8 regime serves (the euclidean family — cross-term math)
QUANT_METRICS = ("sqeuclidean", "euclidean")


def _merge_topk(best_d_ref, best_i_ref, dtile, cols, *, k: int):
    """Merge a finished (bm, bn) distance tile into the (bm, k) running
    top-k: k rounds of min-extraction over the (bm, k + bn) concatenation."""
    cat_d = jnp.concatenate([best_d_ref[...], dtile], axis=1)
    cat_i = jnp.concatenate([best_i_ref[...], cols], axis=1)
    bm, width = cat_d.shape
    iot = jax.lax.broadcasted_iota(jnp.int32, (bm, width), 1)
    vals, idxs = [], []
    for _ in range(k):
        v = jnp.min(cat_d, axis=1)
        ismin = cat_d == v[:, None]
        pos = jnp.min(jnp.where(ismin, iot, width), axis=1)  # first minimum
        sel = iot == pos[:, None]
        idx = jnp.sum(jnp.where(sel, cat_i, 0), axis=1)
        vals.append(v)
        idxs.append(idx)
        cat_d = jnp.where(sel, jnp.inf, cat_d)
    best_d_ref[...] = jnp.stack(vals, axis=1)
    best_i_ref[...] = jnp.stack(idxs, axis=1)


def _mask_tile(dtile, i, j, vtile, *, bm, bn, n, exclude_self):
    """+inf out padded columns (global col >= n), masked candidates
    (``vtile`` (1, bn) 0/1, broadcast over query rows) and, for self-scans,
    the diagonal global_row == global_col."""
    cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    dtile = jnp.where(cols >= n, jnp.inf, dtile)
    if vtile is not None:
        dtile = jnp.where(vtile == 0.0, jnp.inf, dtile)
    if exclude_self:
        rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        dtile = jnp.where(rows == cols, jnp.inf, dtile)
    return dtile, cols


def _select_and_store(best_d, best_i, o_d_ref, o_i_ref, dtile, i, j,
                      *, bm, bn, n, k, n_steps, exclude_self, vtile=None):
    """Shared epilogue: mask, conditional merge, final store."""
    dtile, cols = _mask_tile(
        dtile, i, j, vtile, bm=bm, bn=bn, n=n, exclude_self=exclude_self
    )
    # the k-th best of the worst row bounds what this tile could improve
    can_improve = jnp.min(dtile) < jnp.max(best_d[:, k - 1])

    @pl.when(can_improve)
    def _merge():
        _merge_topk(best_d, best_i, dtile, cols, k=k)

    @pl.when(j == n_steps - 1)
    def _store():
        o_d_ref[...] = best_d[...]
        o_i_ref[...] = best_i[...]


def _matmul_kernel(*refs, metric: str, k: int, n: int, k_steps: int,
                   n_steps: int, bm: int, bn: int, exclude_self: bool,
                   has_valid: bool):
    if has_valid:
        x_ref, y_ref, v_ref, o_d_ref, o_i_ref, acc, sx, sy, best_d, best_i = refs
    else:
        x_ref, y_ref, o_d_ref, o_i_ref, acc, sx, sy, best_d, best_i = refs
        v_ref = None
    i, j, ks = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (ks == 0))
    def _init_best():
        best_d[...] = jnp.full_like(best_d, jnp.inf)
        best_i[...] = jnp.full_like(best_i, -1)

    @pl.when(ks == 0)
    def _init_acc():
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

    @pl.when(ks == k_steps - 1)
    def _epilogue():
        dotv = acc[...]
        if metric == "dot":
            dtile = -dotv
        elif metric == "cosine":
            nx = jnp.sqrt(jnp.maximum(sx[...], EPS))  # (bm, 1)
            ny = jnp.sqrt(jnp.maximum(sy[...], EPS))  # (bn, 1)
            dtile = 1.0 - dotv / (nx * ny.T)
        else:
            d2 = jnp.maximum(sx[...] + sy[...].T - 2.0 * dotv, 0.0)
            dtile = jnp.sqrt(d2) if metric == "euclidean" else d2
        _select_and_store(
            best_d, best_i, o_d_ref, o_i_ref, dtile, i, j, bm=bm, bn=bn,
            n=n, k=k, n_steps=n_steps, exclude_self=exclude_self,
            vtile=None if v_ref is None else v_ref[...],
        )


def _cube_kernel(*refs, metric: str, k: int, n: int, k_steps: int,
                 n_steps: int, bm: int, bn: int, exclude_self: bool,
                 has_valid: bool):
    if has_valid:
        x_ref, y_ref, v_ref, o_d_ref, o_i_ref, dist, best_d, best_i = refs
    else:
        x_ref, y_ref, o_d_ref, o_i_ref, dist, best_d, best_i = refs
        v_ref = None
    i, j, ks = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (ks == 0))
    def _init_best():
        best_d[...] = jnp.full_like(best_d, jnp.inf)
        best_i[...] = jnp.full_like(best_i, -1)

    @pl.when(ks == 0)
    def _init_dist():
        dist[...] = jnp.zeros_like(dist)

    dist[...] = cube_accumulate(
        dist[...], x_ref[...].astype(jnp.float32),
        y_ref[...].astype(jnp.float32), metric=metric,
    )

    @pl.when(ks == k_steps - 1)
    def _epilogue():
        _select_and_store(
            best_d, best_i, o_d_ref, o_i_ref, dist[...], i, j, bm=bm, bn=bn,
            n=n, k=k, n_steps=n_steps, exclude_self=exclude_self,
            vtile=None if v_ref is None else v_ref[...],
        )


def _int8_kernel(*refs, metric: str, k: int, n: int, k_steps: int,
                 n_steps: int, bm: int, bn: int, exclude_self: bool,
                 has_valid: bool):
    """Int8 regime: codes arrive as int8, the cross term runs on the MXU in
    int8 x int8 -> int32, and dequantization happens once per finished tile
    in f32: ``d2 = |q|^2 + |dec(c)|^2 - 2 * alpha_row * acc`` (alpha is the
    per-query scale of the scale-folded, row-quantized query; both squared
    norms are precomputed operands)."""
    if has_valid:
        (x_ref, y_ref, alpha_ref, xn_ref, yn_ref, v_ref,
         o_d_ref, o_i_ref, acc, best_d, best_i) = refs
    else:
        (x_ref, y_ref, alpha_ref, xn_ref, yn_ref,
         o_d_ref, o_i_ref, acc, best_d, best_i) = refs
        v_ref = None
    i, j, ks = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (ks == 0))
    def _init_best():
        best_d[...] = jnp.full_like(best_d, jnp.inf)
        best_i[...] = jnp.full_like(best_i, -1)

    @pl.when(ks == 0)
    def _init_acc():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(ks == k_steps - 1)
    def _epilogue():
        cross = acc[...].astype(jnp.float32) * alpha_ref[...]  # (bm, bn)
        d2 = jnp.maximum(xn_ref[...] + yn_ref[...] - 2.0 * cross, 0.0)
        dtile = jnp.sqrt(d2) if metric == "euclidean" else d2
        _select_and_store(
            best_d, best_i, o_d_ref, o_i_ref, dtile, i, j, bm=bm, bn=bn,
            n=n, k=k, n_steps=n_steps, exclude_self=exclude_self,
            vtile=None if v_ref is None else v_ref[...],
        )


def _call_common(M, N, grid, k, bm, bn, bk, interpret):
    """Grid/spec/output plumbing shared by the f32 and int8 entry points.
    Operand order: X-like (bm, bk), Y-like (bn, bk), [extras...], and —
    when masked — the (1, bn) valid tile riding immediately before the
    outputs."""
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
        pl.BlockSpec((bn, bk), lambda i, j, s: (j, s)),
    ]
    return dict(
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, k), lambda i, j, s: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j, s: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, k), jnp.float32),
            jax.ShapeDtypeStruct((M, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )


_VALID_SPEC = lambda bn: pl.BlockSpec((1, bn), lambda i, j, s: (0, j))


def _pad_valid(valid, n, N):
    """(n,) bool-ish -> (1, N) f32 0/1 operand (padding columns 0 — they
    are also masked by the col >= n guard, belt and braces)."""
    v = jnp.asarray(valid).astype(jnp.float32).reshape(1, n)
    return jnp.pad(v, ((0, 0), (0, N - n)))


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "bm", "bn", "bk", "exclude_self", "interpret"),
)
def topk_pallas(
    X: jax.Array,
    Y: jax.Array,
    *,
    k: int,
    metric: str = "sqeuclidean",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    exclude_self: bool = False,
    valid: jax.Array | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused scan: k nearest rows of Y for every row of X.

    Returns (dists (m, k) f32 ascending, idxs (m, k) int32; -1 where fewer
    than k valid candidates exist).  The (m, n) distance matrix is never
    materialized in HBM.  ``exclude_self`` masks global_row == global_col
    (callers must pass X is Y row-aligned for it to mean "self");
    ``valid`` (n,) bool masks candidates out entirely — they surface only
    as (-1, +inf) "no result" slots, exactly like the jnp scan path.
    """
    if metric not in SUPPORTED:
        raise ValueError(f"topk kernel does not support metric {metric!r}")
    if interpret is None:
        interpret = default_interpret()
    m, d = X.shape
    n, d2 = Y.shape
    assert d == d2, (X.shape, Y.shape)
    k = int(k)

    pm, pn, pk = (-m) % bm, (-n) % bn, (-d) % bk
    Xp = jnp.pad(X, ((0, pm), (0, pk)))
    Yp = jnp.pad(Y, ((0, pn), (0, pk)))
    M, N, K = Xp.shape[0], Yp.shape[0], Xp.shape[1]
    grid = (M // bm, N // bn, K // bk)

    has_valid = valid is not None
    kw = dict(
        metric=metric, k=k, n=n, k_steps=grid[2], n_steps=grid[1],
        bm=bm, bn=bn, exclude_self=exclude_self, has_valid=has_valid,
    )
    common = _call_common(M, N, grid, k, bm, bn, bk, interpret)
    args = (Xp, Yp)
    if has_valid:
        common["in_specs"].append(_VALID_SPEC(bn))
        args = args + (_pad_valid(valid, n, N),)
    select_scratch = [
        pltpu.VMEM((bm, k), jnp.float32),  # running top-k distances
        pltpu.VMEM((bm, k), jnp.int32),  # running top-k indices
    ]
    if metric in MATMUL_METRICS:
        dists, idxs = pl.pallas_call(
            functools.partial(_matmul_kernel, **kw),
            scratch_shapes=[
                pltpu.VMEM((bm, bn), jnp.float32),
                pltpu.VMEM((bm, 1), jnp.float32),
                pltpu.VMEM((bn, 1), jnp.float32),
            ] + select_scratch,
            **common,
        )(*args)
    else:
        dists, idxs = pl.pallas_call(
            functools.partial(_cube_kernel, **kw),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] + select_scratch,
            **common,
        )(*args)
    dists, idxs = dists[:m], idxs[:m]
    # selections from padded columns (possible only when k > #valid) -> -1
    idxs = jnp.where(idxs >= n, -1, idxs)
    return dists, idxs


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "bm", "bn", "bk", "interpret"),
)
def topk_quant_pallas(
    Q: jax.Array,
    codes: jax.Array,
    scales: jax.Array,
    sqnorms: jax.Array,
    *,
    k: int,
    metric: str = "euclidean",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    valid: jax.Array | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused int8 scan: k nearest corpus codes for every f32 query row.

    ``codes`` (n, d) int8 / ``scales`` (d,) f32 / ``sqnorms`` (n,) f32 are
    a ``core/quant.QuantStore.device_view()``.  The query side is prepared
    here, once per batch: fold the corpus scales into the query
    (``x~ = q * s``, so the cross term becomes an integer matmul against
    the raw codes) and row-quantize it with its own absmax ``alpha``.  The
    kernel then computes ``d^2 ~= |q|^2 + |dec(c)|^2 - 2*alpha*(xq . c)``
    per tile — approximate by one extra query-side quantization step vs
    the jnp dequant fallback, which the engines' exact f32 rerank absorbs.
    """
    if metric not in QUANT_METRICS:
        raise ValueError(f"int8 topk regime does not support metric {metric!r}")
    if interpret is None:
        interpret = default_interpret()
    m, d = Q.shape
    n, d2 = codes.shape
    assert d == d2, (Q.shape, codes.shape)
    k = int(k)

    Q = Q.astype(jnp.float32)
    xs = Q * scales[None, :]
    alpha = quant_lib.absmax_scales(xs, axis=1, keepdims=True)  # (m, 1)
    xq = quant_lib.encode(xs, alpha)
    xn = jnp.sum(Q * Q, axis=1, keepdims=True)  # (m, 1)

    pm, pn, pk = (-m) % bm, (-n) % bn, (-d) % bk
    Xq = jnp.pad(xq, ((0, pm), (0, pk)))
    Yq = jnp.pad(codes, ((0, pn), (0, pk)))
    M, N, K = Xq.shape[0], Yq.shape[0], Xq.shape[1]
    grid = (M // bm, N // bn, K // bk)

    has_valid = valid is not None
    kw = dict(
        metric=metric, k=k, n=n, k_steps=grid[2], n_steps=grid[1],
        bm=bm, bn=bn, exclude_self=False, has_valid=has_valid,
    )
    common = _call_common(M, N, grid, k, bm, bn, bk, interpret)
    common["in_specs"].extend([
        pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)),  # alpha
        pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)),  # |q|^2
        pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),  # |dec(c)|^2
    ])
    args = (
        Xq, Yq,
        jnp.pad(alpha, ((0, pm), (0, 0)), constant_values=1.0),
        jnp.pad(xn, ((0, pm), (0, 0))),
        jnp.pad(sqnorms.reshape(1, n), ((0, 0), (0, pn))),
    )
    if has_valid:
        common["in_specs"].append(_VALID_SPEC(bn))
        args = args + (_pad_valid(valid, n, N),)
    dists, idxs = pl.pallas_call(
        functools.partial(_int8_kernel, **kw),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),  # int8 MXU accumulator
            pltpu.VMEM((bm, k), jnp.float32),
            pltpu.VMEM((bm, k), jnp.int32),
        ],
        **common,
    )(*args)
    dists, idxs = dists[:m], idxs[:m]
    idxs = jnp.where(idxs >= n, -1, idxs)
    return dists, idxs
