"""Pallas TPU kernel: embedding bag (ragged gather + segment reduce).

The recsys hot path (kernel_taxonomy §B.6): JAX has no native EmbeddingBag, so
this kernel implements the idiomatic TPU pattern — bag indices are **scalar-
prefetched into SMEM** so the BlockSpec index_map can select which table row
to DMA for each grid step.  The MXU never sees the gather; rows stream
HBM -> VMEM one (1, D) block at a time and accumulate on the VPU.

grid = (B, S): step (b, s) DMAs ``table[ids[b, s]]`` and adds it into
``out[b]``.  Padding ids (< 0) are clamped to row 0 and masked by weight 0 —
the DMA still happens (static schedule), which is exactly how production TPU
embedding kernels keep the pipeline dense.

On real hardware one would add multiple-rows-per-step (S tiling) and a
revisiting-output accumulator; this shape is kept minimal because the
container validates in interpret mode only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(ids_ref, w_ref, table_ref, o_ref, *, s_steps: int):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[0, 0]  # scalar weight for (b, s) — 0.0 for padding
    o_ref[...] += w * table_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("combine", "interpret"))
def embedding_bag_pallas(
    table: jax.Array,
    ids: jax.Array,
    weights: jax.Array | None = None,
    *,
    combine: str = "sum",
    interpret: bool = True,
) -> jax.Array:
    """table (V, D), ids (B, S) -> (B, D) with sum/mean combine."""
    V, D = table.shape
    B, S = ids.shape
    valid = (ids >= 0).astype(jnp.float32)
    w = valid if weights is None else weights.astype(jnp.float32) * valid
    safe_ids = jnp.maximum(ids, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # safe_ids lands in SMEM, visible to index_maps
        grid=(B, S),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, s, ids_sref: (b, s)),  # weight
            pl.BlockSpec((1, D), lambda b, s, ids_sref: (ids_sref[b, s], 0)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda b, s, ids_sref: (b, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_bag_kernel, s_steps=S),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(safe_ids, w, table)
    if combine == "mean":
        denom = jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-9)
        out = out / denom
    return out
