"""The benchmark's exact reference: a plain blocked k-nearest scan.

Independent of the program: squared euclidean distances of a block of
queries against a block of corpus rows as one matmul (at ``HIGHEST``, full
float32, for the reference; the control passes a lower-precision product),
a running top-k carried across corpus blocks.  The served answers are then judged in
float64 on the host (``compare.py``), so the reference only has to pick the
right rows.

A corpus row-sharded over several devices is scanned shard by shard, each
on its own device; the shards' lists, with their row offsets added, are
merged on the host.  The corpus is never gathered onto one device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def highest_dot(a, b):
    """``a @ b.T`` in full float32."""
    return jnp.dot(a, b.T, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("k", "col_block", "dot"))
def _topk_block(Q, X, *, k: int, col_block: int, dot):
    n = X.shape[0]
    nb = n // col_block
    q2 = jnp.sum(Q * Q, axis=1, keepdims=True)

    def body(b, carry):
        best_d, best_i = carry
        xb = jax.lax.dynamic_slice_in_dim(X, b * col_block, col_block, 0)
        x2 = jnp.sum(xb * xb, axis=1)[None, :]
        d2 = q2 + x2 - 2.0 * dot(Q, xb)
        cols = b * col_block + jnp.arange(col_block, dtype=jnp.int32)
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(cols, d2.shape)], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        return -neg, jnp.take_along_axis(cat_i, pos, axis=1)

    m = Q.shape[0]
    init = (jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32))
    d2, idx = jax.lax.fori_loop(0, nb, body, init)
    return jnp.sqrt(jnp.maximum(d2, 0.0)), idx


def col_block_for(n: int, cap: int = 8192) -> int:
    """The largest divisor of ``n`` that is at most ``cap``."""
    for m in range(-(-n // cap), n + 1):
        if n % m == 0:
            return n // m
    return n


def row_shards(X) -> list:
    """``(first row, rows)`` of each row range of ``X`` held on a device,
    in row order: one per shard of a row-sharded array, ``[(0, X)]`` for
    an array on one device or on the host."""
    shards = getattr(X, "addressable_shards", None)
    if shards is None or len(shards) == 1:
        return [(0, X)]
    return sorted(((sh.index[0].start or 0, sh.data) for sh in shards),
                  key=lambda kv: kv[0])


def _query_blocks(Q, query_block: int):
    """``(block, real rows)`` of ``Q`` in blocks of one size, the last one
    padded with copies of its last row."""
    m = Q.shape[0]
    qb = min(query_block, max(8, m))
    for s in range(0, m, qb):
        blk = Q[s:s + qb]
        pad = qb - blk.shape[0]
        if pad:
            blk = np.concatenate([blk, np.repeat(blk[-1:], pad, 0)])
        yield blk, qb - pad


def exact_topk(Q, X, k: int, *, dot=highest_dot, query_block: int = 1024,
               col_cap: int = 8192):
    """Exact euclidean top-``k`` of every row of ``Q`` over ``X``:
    (dist (m, k), idx (m, k)) as numpy, ascending.  Queries go in blocks of
    ``query_block`` (the last one padded), corpus rows in blocks that
    divide ``n`` (for a row-sharded ``X``: that divide a shard, scanned on
    the shard's device, the lists merged on the host by (distance, id),
    so ties go to the lowest id)."""
    Q = np.asarray(Q, np.float32)
    shards = row_shards(X)
    if len(shards) > 1:
        return _exact_topk_sharded(Q, shards, k, dot=dot,
                                   query_block=query_block, col_cap=col_cap)
    cb = col_block_for(X.shape[0], col_cap)
    outs_d, outs_i = [], []
    for blk, real in _query_blocks(Q, query_block):
        d, i = _topk_block(jnp.asarray(blk), X, k=k, col_block=cb, dot=dot)
        outs_d.append(np.asarray(d)[:real])
        outs_i.append(np.asarray(i)[:real])
    return np.concatenate(outs_d), np.concatenate(outs_i)


def _exact_topk_sharded(Q, shards, k, *, dot, query_block, col_cap):
    outs_d, outs_i = [], []
    for blk, real in _query_blocks(Q, query_block):
        # every shard's scan is dispatched before any is read back, so the
        # devices scan at once
        parts = []
        for first, Xs in shards:
            cb = col_block_for(Xs.shape[0], col_cap)
            parts.append((first, _topk_block(jax.device_put(blk, Xs.sharding),
                                             Xs, k=k, col_block=cb, dot=dot)))
        dist = np.concatenate([np.asarray(d)[:real] for _, (d, _) in parts], 1)
        idx = np.concatenate([np.asarray(i)[:real].astype(np.int64) + first
                              for first, (_, i) in parts], 1)
        order = np.lexsort((idx, dist), axis=1)[:, :k]
        outs_d.append(np.take_along_axis(dist, order, 1))
        outs_i.append(np.take_along_axis(idx, order, 1))
    return np.concatenate(outs_d), np.concatenate(outs_i)


def dist64(X_rows: np.ndarray, Q: np.ndarray) -> np.ndarray:
    """float64 euclidean distance of rows (m, k, d) to their queries (m, d)."""
    diff = X_rows.astype(np.float64) - Q[:, None, :].astype(np.float64)
    return np.sqrt(np.sum(diff * diff, axis=-1))
