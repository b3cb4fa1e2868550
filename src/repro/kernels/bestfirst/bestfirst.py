"""Pallas TPU kernel: the whole best-first traversal (Algorithm 2) of a batch.

``core/vptree._best_first_impl`` runs the traversal as an XLA ``while``
whose body is some thirty small operations per comparison: on a TPU each
is a launch of its own, so a step costs ~10 µs of which little is work,
and a profiler records every one of them.  This kernel runs the same loop
on the TensorCore's scalar unit, for all B queries of the batch in
lockstep, as one operation.  It is the same algorithm, step for step: the
same DFS stack, the same prune rules, the same sorted top-k insert.  Ids,
distances and comparison counts equal the XLA loop's (tests compare them
in interpret mode; the float sums are in another order, so on a chip a
distance can differ in its last bit).

Per step, for every query still live (stack not empty, budget not spent):

1. pop a node from its stack (SMEM) and DMA the node's record row from HBM
   into SMEM.  The record table packs ``(vantage, mu bits, left, right)``
   of 32 nodes per 128-lane row, since a DMA moves whole lane rows;
2. DMA the vantage's corpus row into VMEM.  The corpus is viewed as
   ``(n / ppr, 128)``: ``ppr`` points of ``seg`` lanes each per row, each
   point's features zero-padded from ``d`` to ``seg``, a power of two;
3. on the vector unit, for all B queries at once: the distance (a masked
   lane sum over the vantage's segment, so padding adds exact zeros), the
   sorted insert into the (B, K) top-k buffer (compare, select and a
   one-lane roll, as ``core/vptree._topk_insert``), ``tau`` and the prune
   tests, packed into one small int per query;
4. back on the scalar unit: the pushes, guarded by the stack's capacity.

A query whose stack empties or whose budget runs out is left alone; the
loop ends when none is live.  Queries are rows of (B, ·) blocks, so B is
any size.  The record table and the corpus rows (``view``) are a relayout
of the whole tree and corpus, so an index builds them once and keeps them.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: record fields per node: vantage, mu (as int32 bits), left, right
REC = 4
NODES_PER_ROW = LANES // REC
INF = float("inf")


def segment(d: int) -> int:
    """Lanes per corpus point: ``d`` rounded up to a power of two."""
    return 1 << max(d - 1, 0).bit_length()


def _kernel(budget_ref, q_ref, rec_hbm, x_hbm,
            kd_ref, ki_ref, comps_ref, trunc_ref,
            stack, sp_s, live_s, base_s, j_s, recs, rows, rec_sem, row_sem,
            *, k: int, cap: int, q: float, seg: int):
    B, Kp = kd_ref.shape
    ppr = LANES // seg
    budget = budget_ref[0]
    kd_ref[...] = jnp.full(kd_ref.shape, INF, jnp.float32)
    ki_ref[...] = jnp.full(ki_ref.shape, -1, jnp.int32)
    for b in range(B):
        stack[b * cap] = 0  # the root
        sp_s[b] = 1
        comps_ref[b] = 0
        trunc_ref[b] = 0
    row_id = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    slot = jax.lax.broadcasted_iota(jnp.int32, (B, Kp), 1)
    lane_seg = jax.lax.broadcasted_iota(jnp.int32, (B, LANES), 1) // seg

    def rec_copy(b, node):
        return pltpu.make_async_copy(
            rec_hbm.at[pl.ds(node // NODES_PER_ROW, 1), :],
            recs.at[pl.ds(b, 1), :], rec_sem.at[b])

    def row_copy(b, j):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(j // ppr, 1), :], rows.at[pl.ds(b, 1), :],
            row_sem.at[b])

    def step(_):
        # 1. pop, fetch the record
        for b in range(B):
            live = (sp_s[b] > 0) & (comps_ref[b] < budget)
            live_s[b] = live.astype(jnp.int32)

            @pl.when(live)
            def _():
                sp = sp_s[b] - 1
                sp_s[b] = sp
                node = stack[b * cap + sp]
                base_s[b] = (node % NODES_PER_ROW) * REC
                rec_copy(b, node).start()

        # 2. fetch the vantage's corpus row
        for b in range(B):
            @pl.when(live_s[b] == 1)
            def _():
                rec_copy(b, 0).wait()
                j = recs[b, base_s[b]]
                j_s[b] = j
                row_copy(b, j).start()
                comps_ref[b] = comps_ref[b] + 1

        live = jnp.zeros((B, 1), jnp.int32)
        jv = jnp.zeros((B, 1), jnp.int32)
        mu_bits = jnp.zeros((B, 1), jnp.int32)
        for b in range(B):
            @pl.when(live_s[b] == 1)
            def _():
                row_copy(b, 0).wait()
            at_b = row_id == b
            live = jnp.where(at_b, live_s[b], live)
            jv = jnp.where(at_b, j_s[b], jv)
            mu_bits = jnp.where(at_b, recs[b, base_s[b] + 1], mu_bits)
        m = jax.lax.bitcast_convert_type(mu_bits, jnp.float32)

        # 3. distance, top-k insert, prune tests: all queries at once
        diff = q_ref[...] - rows[...]
        sq = jnp.where(lane_seg == jv % ppr, diff * diff, 0.0)
        d = jnp.sqrt(jnp.maximum(jnp.sum(sq, axis=1, keepdims=True), 0.0))
        ins = jnp.where(live == 1, d, INF)  # +inf: a no-op insert
        kd, ki = kd_ref[...], ki_ref[...]
        prev_kd, prev_ki = pltpu.roll(kd, 1, 1), pltpu.roll(ki, 1, 1)
        keep = jnp.logical_not(ins < kd)  # slot before the insert point
        here = (slot == 0) | jnp.logical_not(ins < prev_kd)
        kd = jnp.where(keep, kd, jnp.where(here, ins, prev_kd))
        ki = jnp.where(keep, ki, jnp.where(here, jv, prev_ki))
        kd_ref[...] = kd
        ki_ref[...] = ki
        tau = kd[:, k - 1:k]
        if math.isinf(q):
            prune_out = jnp.maximum(d, tau) < m
            prune_in = jnp.maximum(m, tau) <= d
        else:
            s = jnp.maximum(jnp.maximum(d, m),
                            jnp.where(jnp.isfinite(tau), tau, 0.0))
            s = jnp.maximum(s, 1e-30)
            dq = (d / s) ** q
            mq = (m / s) ** q
            tq = jnp.where(jnp.isfinite(tau), (tau / s) ** q, INF)
            prune_out = dq + tq < mq
            prune_in = mq + tq <= dq
        tests = (prune_in.astype(jnp.int32) + 2 * prune_out.astype(jnp.int32)
                 + 4 * (d < m).astype(jnp.int32))

        # 4. pushes: the deferred far child first, the near child last
        n_live = jnp.int32(0)
        for b in range(B):
            @pl.when(live_s[b] == 1)
            def _():
                t = jnp.sum(jnp.where(row_id == b, tests, 0))
                near_left = (t & 4) != 0
                lc = recs[b, base_s[b] + 2]
                rc = recs[b, base_s[b] + 3]
                push_left = (lc >= 0) & ((t & 1) == 0)
                push_right = (rc >= 0) & ((t & 2) == 0)
                first = jnp.where(near_left, rc, lc)
                first_ok = jnp.where(near_left, push_right, push_left)
                second = jnp.where(near_left, lc, rc)
                second_ok = jnp.where(near_left, push_left, push_right)
                sp = sp_s[b]
                room1 = sp < cap
                do1 = first_ok & room1

                @pl.when(do1)
                def _():
                    stack[b * cap + sp] = first

                sp = sp + do1.astype(jnp.int32)
                room2 = sp < cap
                do2 = second_ok & room2

                @pl.when(do2)
                def _():
                    stack[b * cap + sp] = second

                sp_s[b] = sp + do2.astype(jnp.int32)
                lost = (first_ok & ~room1) | (second_ok & ~room2)
                trunc_ref[b] = trunc_ref[b] | lost.astype(jnp.int32)

            n_live = n_live + ((sp_s[b] > 0)
                               & (comps_ref[b] < budget)).astype(jnp.int32)
        return n_live

    jax.lax.while_loop(lambda n_live: n_live > 0, step, jnp.int32(1))


class View(NamedTuple):
    """The kernel's layout of a tree and its corpus, built once per index."""

    records: jax.Array  # (ceil(n / 32), 128) int32: 32 node records a row
    rows: jax.Array  # (ceil(n / ppr), 128) float32: ppr corpus points a row


@jax.jit
def view(tree_arrays, X: jax.Array) -> View:
    """Node ``i``'s ``(vantage, mu bits, left, right)`` at row ``i // 32``,
    lanes ``4 (i % 32)`` on; point ``j`` at row ``j // ppr``, lanes
    ``seg (j % ppr)`` on, zero-padded from ``d`` to ``seg`` features."""
    vantage, mu, left, right = tree_arrays
    rec = jnp.stack([vantage, jax.lax.bitcast_convert_type(mu, jnp.int32),
                     left, right], axis=1).astype(jnp.int32)
    n_rec = -(-rec.shape[0] // NODES_PER_ROW)
    rec = jnp.pad(rec, ((0, n_rec * NODES_PER_ROW - rec.shape[0]), (0, 0)))
    n, d = X.shape
    if d > LANES:
        raise ValueError(f"at most {LANES} features, got {d}")
    seg = segment(d)
    ppr = LANES // seg
    n_rows = -(-n // ppr)
    Xp = jnp.pad(X.astype(jnp.float32), ((0, n_rows * ppr - n), (0, seg - d)))
    return View(rec.reshape(n_rec, LANES), Xp.reshape(n_rows, LANES))


@functools.partial(
    jax.jit, static_argnames=("q", "k", "stack_cap", "interpret"))
def best_first_pallas(
    tv: View, queries, max_comparisons, *, q: float, k: int, stack_cap: int,
    interpret: bool = False,
):
    """Best-first search of every query under the Euclidean metric, as
    ``core/vptree._best_first_impl`` with ``valid=None``, over ``tv =
    view(tree_arrays, X)``: returns (idx (B, k), dist (B, k), comparisons
    (B,), truncated (B,)).  ``max_comparisons`` is a traced int."""
    B, d = queries.shape
    seg = segment(d)
    Kp = -(-k // LANES) * LANES  # a prefix of a longer sorted buffer
    qt = jnp.pad(queries.astype(jnp.float32), ((0, 0), (0, seg - d)))
    qt = jnp.tile(qt, (1, LANES // seg))  # every segment holds the query
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    kd, ki, comps, trunc = pl.pallas_call(
        functools.partial(_kernel, k=k, cap=stack_cap, q=float(q), seg=seg),
        in_specs=[smem, vmem, hbm, hbm],
        out_specs=[vmem, vmem, smem, smem],
        out_shape=[jax.ShapeDtypeStruct((B, Kp), jnp.float32),
                   jax.ShapeDtypeStruct((B, Kp), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32)],
        scratch_shapes=[
            pltpu.SMEM((B * stack_cap,), jnp.int32),  # stacks
            pltpu.SMEM((B,), jnp.int32),  # stack pointers
            pltpu.SMEM((B,), jnp.int32),  # live this step
            pltpu.SMEM((B,), jnp.int32),  # record's lane in its row
            pltpu.SMEM((B,), jnp.int32),  # vantage id
            pltpu.SMEM((B, LANES), jnp.int32),  # record rows
            pltpu.VMEM((B, LANES), jnp.float32),  # corpus rows
            pltpu.SemaphoreType.DMA((B,)),
            pltpu.SemaphoreType.DMA((B,)),
        ],
        interpret=interpret,
    )(jnp.reshape(max_comparisons, (1,)).astype(jnp.int32), qt, tv.records,
      tv.rows)
    return ki[:, :k], kd[:, :k], comps, trunc.astype(bool)
