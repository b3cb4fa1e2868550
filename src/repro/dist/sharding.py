"""Sharding policies: logical-axis rules -> PartitionSpecs (DESIGN.md §6).

Models never name mesh axes.  They declare parameters with *logical* axis
names (``models/params.py``) and wrap activations in ``act(dctx, x, *names)``;
a per-(arch, mesh, input-shape) policy maps those names to mesh axes:

* ``w_rules`` — logical weight axis -> mesh axis (None = replicated).  The
  derived ``DistCtx.shard_w(decls)`` tree of PartitionSpecs drives
  ``jax.device_put`` / ``in_shardings``.
* ``a_rules`` — activation axis name -> mesh axis, applied as
  ``with_sharding_constraint`` inside the model so GSPMD keeps the layout
  the policy chose instead of re-deriving one per op.

``lm_policy`` encodes the standard decision tree: tensor-parallel attention
over heads when the head count divides the model axis (else sequence-parallel
attention), FSDP over the data axis above a parameter threshold, expert
sharding per ``models.moe.ep_mode``, and decode-time KV-cache sequence
sharding that absorbs whichever axes the (tiny) decode batch cannot use.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import params as plib

# FSDP pays one weight all-gather per layer; below ~1B parameters the
# weights fit replicated and the gather is pure overhead.
FSDP_PARAM_THRESHOLD = 1_000_000_000


@dataclasses.dataclass
class DistCtx:
    """Mesh + resolved rules for one (arch, mesh, shape) cell."""

    mesh: Any
    w_rules: dict[str, Any]
    a_rules: dict[str, Any]
    options: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def batch_axes(self) -> tuple[str, ...]:
        b = self.a_rules.get("batch")
        if b is None:
            return ()
        return tuple(b) if isinstance(b, (tuple, list)) else (b,)

    def opt(self, key: str, default: Any = None) -> Any:
        return self.options.get(key, default)

    def shard_w(self, decls) -> Any:
        """Param declarations -> PartitionSpec tree via w_rules."""
        return jax.tree_util.tree_map(
            lambda p: P(*(self.w_rules.get(n) for n in p.logical)),
            decls,
            is_leaf=plib.is_param,
        )


def act(dctx: Optional[DistCtx], x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Constrain activation ``x`` so dim i lives on ``a_rules[names[i]]``.

    ``None`` entries (either the name or an unmapped rule) replicate that
    dim.  No-op without a ctx so single-device paths stay constraint-free.
    """
    if dctx is None:
        return x
    spec = P(*(dctx.a_rules.get(n) if n is not None else None for n in names))
    return jax.lax.with_sharding_constraint(x, NamedSharding(dctx.mesh, spec))


# ---------------------------------------------------------------------------
# policy helpers
# ---------------------------------------------------------------------------

def _axis(mesh, name: str) -> int:
    return int(mesh.shape.get(name, 1))


def _batch_rule(mesh, batch: int):
    """Shard the batch over (pod, data) — largest prefix that divides it."""
    axes = [a for a in ("pod", "data") if _axis(mesh, a) > 1]
    while axes:
        shards = math.prod(_axis(mesh, a) for a in axes)
        if batch % shards == 0 and batch >= shards:
            return tuple(axes) if len(axes) > 1 else axes[0]
        axes.pop(0)  # drop pod first, then give up
    return None


# ---------------------------------------------------------------------------
# LM policy
# ---------------------------------------------------------------------------

def lm_policy(
    cfg,
    mesh,
    *,
    kind: str = "train",
    batch: int = 1,
    fsdp: Optional[bool] = None,
    moe_impl: str = "gathered",
) -> DistCtx:
    msz = _axis(mesh, "model")
    tp_heads = msz > 1 and cfg.num_heads % msz == 0
    if fsdp is None:
        from repro.models.transformer import lm_decls

        fsdp = plib.param_count(lm_decls(cfg)) >= FSDP_PARAM_THRESHOLD
    fsdp_axis = "data" if (fsdp and _axis(mesh, "data") > 1) else None

    w_rules: dict[str, Any] = {
        "layers": None,
        # embedding table: vocab rows over model, d_model over the FSDP axis
        "vocab_in": "model" if (msz > 1 and cfg.vocab_size % msz == 0) else None,
        "embed_tbl": fsdp_axis,
        "vocab": "model" if (msz > 1 and cfg.vocab_size % msz == 0) else None,
        "embed": fsdp_axis,
        "embed2": None,
        # attention: TP over heads when divisible, else replicated weights
        "q_heads": "model" if tp_heads else None,
        "kv_heads": "model" if (tp_heads and cfg.num_kv_heads % msz == 0) else None,
        "head_dim": None,
        "q_lora": None,
        "kv_lora": None,
        # dense MLP: megatron column/row split over model
        "mlp": "model" if (msz > 1 and cfg.d_ff % msz == 0) else None,
        "experts_r": None,
    }
    if cfg.moe:
        from repro.models.moe import ep_mode

        if moe_impl == "zero3":
            w_rules.update(experts="model", embed_x="data", expert_mlp=None)
        else:
            mode = ep_mode(cfg, mesh)
            if mode == "2d":
                w_rules.update(experts=("model", "data"), embed_x=None, expert_mlp=None)
            elif mode == "fslice":
                w_rules.update(experts="model", embed_x=None, expert_mlp="data")
            else:
                w_rules.update(experts="model", embed_x=None, expert_mlp=None)

    batch_rule = _batch_rule(mesh, batch)
    a_rules: dict[str, Any] = {
        "batch": batch_rule,
        "seq": None,
        # no TP over heads -> shard the attention inputs over sequence instead
        "attn_seq": None if tp_heads else ("model" if msz > 1 else None),
        "embed_act": None,
        "vocab": w_rules["vocab"],
        "layers": None,
        "kv_heads": w_rules["kv_heads"],
        "head_dim": None,
        "kv_lora": None,
        "rope": None,
        "kv_seq": None,
    }
    if kind == "decode":
        # decode batches are small: the KV-cache sequence axis absorbs the
        # model axis, plus the data axis when the batch can't use it.
        a_rules["kv_seq"] = "model" if batch_rule is not None else ("data", "model")
    elif kind == "prefill":
        a_rules["kv_seq"] = "model" if tp_heads else None
    return DistCtx(
        mesh=mesh, w_rules=w_rules, a_rules=a_rules,
        options={"moe_impl": moe_impl, "kind": kind, "fsdp": bool(fsdp)},
    )


# ---------------------------------------------------------------------------
# GNN / RecSys policies
# ---------------------------------------------------------------------------

def gnn_policy(cfg, mesh) -> DistCtx:
    """Full-graph GCN: tiny weights stay replicated; the edge list (the only
    O(E) tensor) shards over every mesh axis."""
    edge_axes = tuple(a for a in ("pod", "data", "model") if _axis(mesh, a) > 1)
    w_rules = {"feat": None, "hidden": None}
    a_rules = {
        "batch": None,
        "edges": edge_axes if len(edge_axes) != 1 else edge_axes[0],
    }
    return DistCtx(mesh=mesh, w_rules=w_rules, a_rules=a_rules)


def search_policy(mesh) -> DistCtx:
    """Sharded vector search (`core/index.ShardedIndex`): the corpus — and
    every per-shard index array stacked on its leading shard axis — lives on
    "data"; query batches are replicated (every shard answers every query)
    and results meet in the host-side running merge."""
    return DistCtx(
        mesh=mesh,
        w_rules={"corpus": "data"},
        a_rules={"batch": None, "corpus": "data"},
    )


def recsys_policy(cfg, mesh, *, batch: int = 1) -> DistCtx:
    """CTR models: the ~38M-row embedding table is row-sharded over every
    axis (dist.embedlookup gathers hit rows); dense tower replicated."""
    all_axes = tuple(a for a in ("pod", "data", "model") if _axis(mesh, a) > 1)
    table_rule = all_axes if len(all_axes) != 1 else (all_axes[0] if all_axes else None)
    w_rules = {
        "table": table_rule,
        "edim": None,
        "hidden": None,  # appears on both dims of MLP weights — keep replicated
        "cin": None,
        "fields": None,
        "heads": None,
        "attn": None,
    }
    a_rules = {
        "batch": _batch_rule(mesh, batch),
        "fields": None,
        "edim": None,
        "cand": table_rule,  # retrieval candidates: sharded like the table
    }
    return DistCtx(mesh=mesh, w_rules=w_rules, a_rules=a_rules)
