"""Mixture-of-Experts FFN with expert parallelism (DESIGN.md §6).

Two execution paths sharing the same parameters:

* ``moe_ffn_dense`` — reference path (no mesh): every expert processes every
  token, outputs combined by routing weights.  Exact (no capacity drops);
  used by smoke tests and as the correctness oracle for the EP path.

* ``moe_ffn_ep``   — production path under ``shard_map``: activations are
  sharded over the batch axes and replicated over the model axis; experts are
  sharded over the model axis.  Each chip sort-free-dispatches its local
  tokens to its local experts (position-in-expert via a (T*k, E_loc) one-hot
  cumsum — E_loc is small, so this stays tiny), runs the expert FFNs as one
  batched (E_loc, C, d) x (E_loc, d, f) matmul, combines weighted outputs,
  and a single psum over the model axis sums the expert groups.  No
  all-to-all is needed because activations are model-replicated (the TP
  psum this replaces would have moved the same bytes).

Routing: softmax (Switch/GShard, qwen3) or sigmoid with top-k renorm
(DeepSeek-V3 aux-free style) + routed scaling.  Capacity-dropped tokens
contribute zero (standard dropped-token semantics).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import LMConfig


MOE_CHUNK_TOKENS = 32768  # gathered tokens processed per EP chunk


def router_probs(x: jax.Array, wr: jax.Array, cfg: LMConfig) -> jax.Array:
    """(B, S, d) -> (B, S, E) routing probabilities (f32)."""
    logits = jnp.einsum("bsd,de->bse", x, wr.astype(x.dtype)).astype(jnp.float32)
    if cfg.router == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def topk_weights(probs: jax.Array, cfg: LMConfig):
    """Top-k selection + renormalization. probs (..., E) f32."""
    top_w, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    scaling = getattr(cfg, "routed_scaling", 1.0)
    return top_w * scaling, top_i


def load_balance_loss(probs: jax.Array, top_i: jax.Array, cfg: LMConfig) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * P_e."""
    E = cfg.num_experts
    pe = jnp.mean(probs.reshape(-1, E), axis=0)
    counts = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    fe = counts / jnp.maximum(jnp.sum(counts), 1.0)
    return E * jnp.sum(fe * pe)


def _slot_maps(top_i, top_w, eo, E_loc: int, C: int, T: int, k: int, dtype):
    """Capacity-slot assignment without materializing (T*k, d) anything.

    Returns slot_tok (E_loc, C) int32 — source token per expert slot (T =
    empty), and slot_w (E_loc, C) — routing weight per slot (0 = empty).
    Position-in-expert comes from a (T*k, E_loc) one-hot cumsum (E_loc is
    per-chip small); capacity overflow lands in a trash column that is
    sliced off.
    """
    flat_i = top_i.reshape(-1)
    flat_w = top_w.reshape(-1).astype(dtype)
    tok = jnp.repeat(jnp.arange(T), k)
    local = (flat_i >= eo) & (flat_i < eo + E_loc)
    lid = jnp.clip(flat_i - eo, 0, E_loc - 1)
    onehot = (lid[:, None] == jnp.arange(E_loc)[None, :]) & local[:, None]
    pos_all = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    pos = jnp.take_along_axis(pos_all, lid[:, None], axis=1)[:, 0]
    keep = local & (pos < C)
    wpos = jnp.where(keep, pos, C)  # C = trash column
    slot_tok = jnp.full((E_loc, C + 1), T, jnp.int32).at[lid, wpos].set(tok.astype(jnp.int32))
    slot_w = jnp.zeros((E_loc, C + 1), dtype).at[lid, wpos].set(flat_w * keep.astype(dtype))
    return slot_tok[:, :C], slot_w[:, :C]


def _expert_ffn(buf: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
                activation: str) -> jax.Array:
    """buf (E, C, d) -> (E, C, d) through per-expert GLU FFNs."""
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(buf.dtype))
    act = jax.nn.silu if activation == "swiglu" else partial(jax.nn.gelu, approximate=True)
    return jnp.einsum("ecf,efd->ecd", act(g) * u, wd.astype(buf.dtype))


# ---------------------------------------------------------------------------
# dense reference path
# ---------------------------------------------------------------------------

def moe_ffn_dense(x: jax.Array, probs: jax.Array, p: dict, cfg: LMConfig) -> jax.Array:
    """All experts on all tokens; exact combine. For tests / tiny configs."""
    B, S, d = x.shape
    top_w, top_i = topk_weights(probs, cfg)  # (B,S,k)
    oh = jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)  # (B,S,k,E)
    full_w = jnp.einsum("bsk,bske->bse", top_w, oh)
    g = jnp.einsum("bsd,edf->bsef", x, p["wg"].astype(x.dtype))
    u = jnp.einsum("bsd,edf->bsef", x, p["wu"].astype(x.dtype))
    act = jax.nn.silu if cfg.activation == "swiglu" else partial(jax.nn.gelu, approximate=True)
    h = jnp.einsum("bsef,efd->bsed", act(g) * u, p["wd"].astype(x.dtype))
    return jnp.einsum("bsed,bse->bsd", h, full_w.astype(x.dtype))


# ---------------------------------------------------------------------------
# expert-parallel path (shard_map)
# ---------------------------------------------------------------------------

def ep_mode(cfg: LMConfig, mesh, *, model_axis="model", data_axis="data") -> str:
    """How expert weights shard (DESIGN.md §6, EXPERIMENTS.md §Perf/H1):

    '2d'     — experts over (model x data): E % (model*data) == 0.
               Every chip owns whole experts; nothing else to slice.
    'fslice' — experts over model, expert d_ff over data.
    'model'  — experts over model only (weights replicated over data — only
               sane for small E*d*f).
    """
    msz = mesh.shape.get(model_axis, 1)
    dsz = mesh.shape.get(data_axis, 1)
    E, f = cfg.num_experts, cfg.moe_d_ff
    if E % (msz * dsz) == 0:
        return "2d"
    if E % msz == 0 and f % dsz == 0:
        return "fslice"
    return "model"


def expert_weight_specs(cfg: LMConfig, mesh, *, model_axis="model", data_axis="data"):
    mode = ep_mode(cfg, mesh, model_axis=model_axis, data_axis=data_axis)
    if mode == "2d":
        e = P((model_axis, data_axis), None, None)
        return mode, {"wg": e, "wu": e, "wd": e}
    if mode == "fslice":
        return mode, {
            "wg": P(model_axis, None, data_axis),
            "wu": P(model_axis, None, data_axis),
            "wd": P(model_axis, data_axis, None),
        }
    e = P(model_axis, None, None)
    return mode, {"wg": e, "wu": e, "wd": e}


def moe_ffn_ep(
    x: jax.Array,
    probs: jax.Array,
    p: dict,
    cfg: LMConfig,
    *,
    mesh,
    batch_axes: tuple[str, ...],
    model_axis: str = "model",
    data_axis: str = "data",
) -> jax.Array:
    """Gathered-token expert parallelism under shard_map.

    Tokens are all-gathered across the data axis (activations are ~25x
    smaller than expert weights at these shapes — gathering tokens instead
    of ZeRO-3-gathering expert weights is what keeps temp memory inside
    HBM; see EXPERIMENTS.md §Perf/H1), every chip dispatches the gathered
    tokens to the experts it owns, and one psum over (data, model) combines
    expert-group and d_ff-slice partials in a single collective.
    """
    E = cfg.num_experts
    k = cfg.num_experts_per_tok
    mode = ep_mode(cfg, mesh, model_axis=model_axis, data_axis=data_axis)
    msz = mesh.shape.get(model_axis, 1)
    dsz = mesh.shape.get(data_axis, 1)
    B, S, d = x.shape
    batch_shards = math.prod(mesh.shape[a] for a in batch_axes)
    do_gather = data_axis in batch_axes and dsz > 1
    gsz = dsz if do_gather else 1
    T_loc = (B // max(batch_shards, 1)) * S  # tokens per chip before gather
    # chunk the token stream so expert buffers stay VMEM/HBM-friendly even
    # for 1M-token prefills: ~MOE_CHUNK_TOKENS gathered tokens per chunk
    tc_loc = max(1, min(T_loc, max(MOE_CHUNK_TOKENS // gsz, 1)))
    while T_loc % tc_loc:
        tc_loc -= 1
    n_chunks = T_loc // tc_loc
    T_g = tc_loc * gsz  # gathered tokens per chunk
    if mode == "2d":
        E_loc = E // (msz * dsz)
    else:
        E_loc = E // msz
    C = max(int(math.ceil(T_g * k / E * cfg.capacity_factor)), 8)
    psum_axes = (
        (model_axis, data_axis) if (mode in ("2d", "fslice") and dsz > 1)
        else (model_axis,)
    )

    def local_moe(x_loc, probs_loc, wg, wu, wd):
        xf_l = x_loc.reshape(T_loc, d)
        pf_l = probs_loc.reshape(T_loc, E)
        if mode == "2d":
            eo = (jax.lax.axis_index(model_axis) * dsz + jax.lax.axis_index(data_axis)) * E_loc
        else:
            eo = jax.lax.axis_index(model_axis) * E_loc

        def chunk_body(_, xc_pc):
            xc, pc = xc_pc  # (tc_loc, d), (tc_loc, E)
            if do_gather:
                xg = jax.lax.all_gather(xc, data_axis, axis=0, tiled=True)
                pg = jax.lax.all_gather(pc, data_axis, axis=0, tiled=True)
            else:
                xg, pg = xc, pc
            top_w, top_i = topk_weights(pg, cfg)
            # slot-map dispatch: scatter token INDICES (not d-wide rows) so
            # nothing of size (T*k, d) materializes (EXPERIMENTS §Perf/H1)
            slot_tok, slot_w = _slot_maps(top_i, top_w, eo, E_loc, C, T_g, k, xg.dtype)
            xg_pad = jnp.concatenate([xg, jnp.zeros((1, d), xg.dtype)])
            buf = xg_pad[slot_tok]  # (E_loc, C, d)
            hbuf = _expert_ffn(buf, wg, wu, wd, cfg.activation)
            contrib = hbuf * slot_w[..., None]
            out = jnp.zeros((T_g + 1, d), xg.dtype).at[slot_tok].add(contrib)[:T_g]
            # one psum folds expert groups (model[, data]) + f-slice partials.
            # NOTE: a psum_scatter over 'data' (reduce-scatter instead of
            # psum+slice) was tried and MEASURED WORSE — its backward pass
            # re-gathers the cotangent, erasing the forward saving
            # (EXPERIMENTS.md §Perf/H1-i4, refuted).
            out = jax.lax.psum(out, psum_axes)
            if do_gather:
                out = jax.lax.dynamic_slice_in_dim(
                    out, jax.lax.axis_index(data_axis) * tc_loc, tc_loc, axis=0
                )
            return None, out

        xs = (xf_l.reshape(n_chunks, tc_loc, d), pf_l.reshape(n_chunks, tc_loc, E))
        _, outs = jax.lax.scan(chunk_body, None, xs)
        return outs.reshape(B // max(batch_shards, 1), S, d)

    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    x_spec = P(bspec, None, None)
    _, wspecs = expert_weight_specs(cfg, mesh, model_axis=model_axis, data_axis=data_axis)
    fn = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(x_spec, x_spec, wspecs["wg"], wspecs["wu"], wspecs["wd"]),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(x, probs, p["wg"], p["wu"], p["wd"])


def moe_ffn_ep_zero3(
    x: jax.Array,
    probs: jax.Array,
    p: dict,
    cfg: LMConfig,
    *,
    mesh,
    batch_axes: tuple[str, ...],
    model_axis: str = "model",
) -> jax.Array:
    """The original formulation kept for the §Perf A/B: experts sharded over
    'model' only, expert weights ZeRO-3 (embed-dim over 'data', re-gathered
    per layer per microbatch by SPMD).  Local dispatch, psum over model."""
    E = cfg.num_experts
    k = cfg.num_experts_per_tok
    model_size = mesh.shape[model_axis]
    assert E % model_size == 0, (E, model_size)
    E_loc = E // model_size
    batch_shards = math.prod(mesh.shape[a] for a in batch_axes)
    B, S, d = x.shape
    T_loc = (B // batch_shards) * S
    C = max(int(math.ceil(T_loc * k / E * cfg.capacity_factor)), 8)

    def local_moe(x_loc, probs_loc, wg, wu, wd):
        Bl = x_loc.shape[0]
        T = Bl * S
        xf = x_loc.reshape(T, d)
        pf = probs_loc.reshape(T, E)
        top_w, top_i = topk_weights(pf, cfg)
        eo = jax.lax.axis_index(model_axis) * E_loc
        slot_tok, slot_w = _slot_maps(top_i, top_w, eo, E_loc, C, T, k, xf.dtype)
        xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
        buf = xf_pad[slot_tok]
        hbuf = _expert_ffn(buf, wg, wu, wd, cfg.activation)
        contrib = hbuf * slot_w[..., None]
        out = jnp.zeros((T + 1, d), xf.dtype).at[slot_tok].add(contrib)[:T]
        return jax.lax.psum(out, model_axis).reshape(Bl, S, d)

    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    x_spec = P(bspec, None, None)
    e_spec = P(model_axis, None, None)
    fn = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(x_spec, x_spec, e_spec, e_spec, e_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(x, probs, p["wg"], p["wu"], p["wd"])
