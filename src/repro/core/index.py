"""Unified index protocol, registry, and the sharded search engine.

One ``build/search`` contract from kernels to serving (DESIGN.md §10):

* ``SearchResult`` — the triple every searcher returns.  ``idx`` (B, k)
  int32 dataset indices (-1 = no result), ``dist`` (B, k) f32 ascending,
  ``comparisons`` (B,) int32 — the engine's distance-evaluation count, the
  paper's implementation-agnostic cost metric (App. F.1).  For the scan
  engines these are original-space candidate scores; for the infinity
  engine they are embedding-space tree visits plus the two-stage rerank
  width (F.5's accounting — the k final original-metric scores attached to
  every result are reporting, not counted search work).
* ``Index`` — the protocol: ``build(X, cfg)`` / ``search(Q, k, budget)`` /
  ``memory_bytes()``.  ``cfg`` is one plain mapping describing the whole
  engine: keys matching the engine's ``build`` signature configure
  construction, keys matching its ``search`` signature become per-instance
  search defaults.
* registry — ``@register_index(name)`` + ``build(name, X, cfg)``.  The five
  built-ins ("brute", "ivf_flat", "ivf_pq", "nsw", "infinity") self-register
  when their modules load; ``_ensure_builtin`` loads them on first lookup so
  importing this module stays cheap.
* ``ShardedIndex`` — the corpus row-sharded over the ``data`` axis of a
  1-axis device mesh via ``shard_map`` (``dist/sharding.py`` conventions:
  corpus rows on "data", queries replicated).  Each shard runs any
  registered engine locally; per-shard top-k lists get their global indices
  back from the shard offsets and are merged with the ``core/scan`` running
  merge, so a multi-device run returns exactly what the single-device
  engine would for exhaustive engines (see DESIGN.md §10 for the argument).
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Any, Mapping, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scan as scan_lib
from repro.core import telemetry as telem


class SearchResult(NamedTuple):
    """Uniform search answer: unpacks as (idx, dist, comparisons)."""

    idx: jax.Array  # (B, k) int32, -1 = no result
    dist: jax.Array  # (B, k) f32, ascending (ties -> lowest index)
    comparisons: jax.Array  # (B,) int32 original-space distance evaluations


@runtime_checkable
class Index(Protocol):
    """What every registered engine implements (structural — no inheritance).

    ``search``'s optional ``filter`` is a predicate spec (``core/filter``
    AST or its dict sugar, compiled against the engine's attribute store —
    the ``attrs`` cfg key at build) or a precomputed ``(n,)`` bool mask;
    engines AND it into their candidate validity so a filtered search only
    answers from passing rows (DESIGN.md §12)."""

    @classmethod
    def build(cls, X, **cfg) -> "Index": ...

    def search(self, Q, k: int = 1, *, budget: Optional[int] = None,
               filter=None) -> SearchResult: ...

    def memory_bytes(self) -> int: ...


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type] = {}
BUILTIN = ("brute", "ivf_flat", "ivf_pq", "nsw", "infinity", "sharded", "live")


def register_index(name: str):
    """Class decorator: expose an engine under a stable string key."""

    def deco(cls):
        for attr in ("build", "search", "memory_bytes"):
            if not hasattr(cls, attr):
                raise TypeError(f"{cls.__name__} lacks Index.{attr}")
        cls.registry_name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def _ensure_builtin() -> None:
    # engines self-register at module load; importing them here keeps the
    # registry lazily populated without import cycles
    import repro.core.baselines  # noqa: F401
    import repro.core.live  # noqa: F401
    import repro.core.search  # noqa: F401


def available() -> tuple[str, ...]:
    _ensure_builtin()
    return tuple(sorted(_REGISTRY))


def list_engines() -> dict[str, str]:
    """{registry key: one-line summary} for every registered engine — the
    operator-facing discovery surface (``serve.py --list-engines``)."""
    _ensure_builtin()
    out = {}
    for name in sorted(_REGISTRY):
        doc = (_REGISTRY[name].__doc__ or "").strip()
        out[name] = doc.splitlines()[0].strip() if doc else ""
    return out


def get_index(name: str) -> type:
    _ensure_builtin()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown index {name!r}; available: {available()}") from None


def build(name: str, X, cfg: Optional[Mapping[str, Any]] = None) -> Index:
    """Build any registered engine from one config mapping.

    Keys are split against the engine's ``build`` / ``search`` signatures;
    leftover search-time keys are stored as the instance's search defaults
    (so ``registry.build("ivf_flat", X, {"num_clusters": 48, "nprobe": 8})``
    probes 8 lists on every subsequent ``search``).

    The reserved key ``attrs`` — ``{column: per-row values}`` — builds a
    columnar ``core/attrs`` store aligned with the corpus rows and attaches
    it to the instance, enabling predicate filters on ``search``.  It is
    handled HERE, once for every engine, so no engine signature carries it;
    engines with structural needs (live's slot capacity, sharded's mesh
    placement) override the ``attach_attrs`` hook.

    The reserved key ``quant`` (truthy) quantizes the corpus to int8 codes
    (``core/quant.QuantStore``) and attaches the store the same way
    (``attach_quant`` hook: live extends to slot capacity and quantizes
    upserts, sharded places codes on its mesh's data axis).  Scan engines
    (brute, ivf_flat, infinity's rerank, live's delta) then run their first
    pass on codes — 1 byte/dim read — and exactly rerank a
    ``quant.shortlist_width``-wide shortlist in f32; engines without a
    corpus-scan stage (nsw's graph walk, ivf_pq's own PQ codes) hold the
    store but search unchanged (DESIGN.md §13).

    The reserved key ``chaos`` — a ``core/chaos.FaultPlan`` or its dict
    sugar — arms deterministic fault injection (DESIGN.md §14): plain
    engines get their ``search`` wrapped with the latency/transient
    injector; sharded and live engines hold the plan and consult it at
    their own fault sites (shard death, compaction publish, delta
    overflow, snapshot corruption).  A ``build``-site fault fires here,
    after construction: the poisoned instance never escapes.
    """
    cls = get_index(name)
    cfg = dict(cfg or {})
    attr_values = cfg.pop("attrs", None)
    quant_cfg = cfg.pop("quant", None)
    chaos_cfg = cfg.pop("chaos", None)
    hook = getattr(cls, "registry_build", None)
    if hook is not None:
        inst = hook(X, cfg)
    else:
        inst = generic_registry_build(cls, X, cfg)
    if attr_values:
        from repro.core import attrs as attrs_lib

        n = int(jnp.asarray(X).shape[0])
        attach_store(inst, attrs_lib.AttributeStore.build(attr_values, n))
    if quant_cfg:
        from repro.core import quant as quant_lib

        attach_quant_store(inst, quant_lib.QuantStore.build(X))
    if chaos_cfg is not None:
        from repro.core import chaos as chaos_lib

        plan = chaos_lib.FaultPlan.from_cfg(chaos_cfg)
        plan.on_build()  # a poisoned build never escapes
        attach_chaos(inst, plan)
    return inst


def attach_store(inst, store) -> None:
    """Attach a built ``AttributeStore`` to an engine instance — through
    its ``attach_attrs`` hook when it has one (live extends to slot
    capacity, sharded places columns on the mesh), else as a plain
    ``attrs`` attribute.  Also the re-attachment path of ``store.load``."""
    hook = getattr(inst, "attach_attrs", None)
    if hook is not None:
        hook(store)
    else:
        inst.attrs = store


def attach_quant_store(inst, store) -> None:
    """Attach a built ``core/quant.QuantStore`` — through the engine's
    ``attach_quant`` hook when it has one (live extends to slot capacity,
    sharded places codes on the mesh's data axis), else as a plain
    ``quant`` attribute.  Also the re-attachment path of ``store.load``
    (format v3)."""
    hook = getattr(inst, "attach_quant", None)
    if hook is not None:
        hook(store)
    else:
        inst.quant = store


def attach_chaos(inst, plan) -> None:
    """Arm an engine instance with a ``core/chaos.FaultPlan`` — through its
    ``attach_chaos`` hook when it has one (sharded draws per-shard deaths,
    live fires compaction/delta faults itself), else by wrapping ``search``
    with the generic injector: every call first runs the plan's ``search``
    site (latency spikes sleep, transient rules raise), then the engine."""
    hook = getattr(inst, "attach_chaos", None)
    if hook is not None:
        hook(plan)
        return
    inst.chaos = plan
    orig = inst.search

    def chaotic_search(*args, **kwargs):
        plan.on_search()
        return orig(*args, **kwargs)

    inst.search = chaotic_search


def side_store_bytes(inst) -> int:
    """Bytes of the per-instance side stores (``attrs`` columns, ``quant``
    codes) — every engine's ``memory_bytes`` adds this so the report covers
    ALL device-resident arrays, not just the engine's own state."""
    total = 0
    for name in ("attrs", "quant"):
        store = getattr(inst, name, None)
        if store is not None:
            total += store.memory_bytes()
    return int(total)


def generic_registry_build(cls, X, cfg: Optional[Mapping[str, Any]]) -> Index:
    cfg = dict(cfg or {})
    bkeys = set(inspect.signature(cls.build).parameters) - {"cls", "X"}
    skeys = (set(inspect.signature(cls.search).parameters) - {"self", "Q", "k"}) | {"budget"}
    bkw = {k: cfg.pop(k) for k in list(cfg) if k in bkeys}
    skw = {k: cfg.pop(k) for k in list(cfg) if k in skeys}
    if cfg:
        raise TypeError(
            f"{cls.registry_name}: unknown cfg keys {sorted(cfg)} "
            f"(build takes {sorted(bkeys)}, search takes {sorted(skeys)})"
        )
    inst = cls.build(X, **bkw)
    inst.search_defaults = skw
    return inst


def resolve(value, defaults: Optional[Mapping[str, Any]], key: str, fallback=None):
    """Search-kwarg resolution order: explicit arg > stored default > fallback."""
    if value is not None:
        return value
    if defaults and defaults.get(key) is not None:
        return defaults[key]
    return fallback


def pytree_nbytes(tree) -> int:
    """Total device bytes of every array leaf (the memory_bytes() helper)."""
    return int(
        sum(
            np.prod(x.shape) * jnp.dtype(x.dtype).itemsize
            for x in jax.tree_util.tree_leaves(tree)
            if hasattr(x, "shape")
        )
    )


def default_merge_shard_static(statics: list[dict]) -> dict:
    """Per-shard static configs must agree (engines with per-shard statics —
    e.g. tree depth — override ``merge_shard_static``)."""
    merged = dict(statics[0])
    for s in statics[1:]:
        if s != merged:
            raise ValueError(f"shard statics disagree: {merged} vs {s}")
    return merged


# ---------------------------------------------------------------------------
# sharded engine
# ---------------------------------------------------------------------------

def _stack_shard_states(states: list):
    """Stack per-shard state pytrees along a new leading shard axis.

    Leaves whose trailing shapes differ across shards (IVF's padded inverted
    lists — Lmax follows the largest cluster) are first padded to the
    elementwise max shape: int leaves with -1 (the codebase-wide "invalid
    id"), float leaves with +inf ("no candidate").
    """
    flats, treedefs = zip(*(jax.tree_util.tree_flatten(s) for s in states))
    stacked = []
    for leaves in zip(*flats):
        leaves = [jnp.asarray(l) for l in leaves]
        shapes = [l.shape for l in leaves]
        if len(set(shapes)) > 1:
            target = tuple(max(s[i] for s in shapes) for i in range(len(shapes[0])))
            fill = -1 if jnp.issubdtype(leaves[0].dtype, jnp.integer) else jnp.inf
            leaves = [
                jnp.pad(
                    l,
                    [(0, t - s) for s, t in zip(l.shape, target)],
                    constant_values=fill,
                )
                for l in leaves
            ]
        stacked.append(jnp.stack(leaves))
    return jax.tree_util.tree_unflatten(treedefs[0], stacked)


@register_index("sharded")
@dataclasses.dataclass
class ShardedIndex:
    """Any registered engine, data-parallel over corpus shards.

    ``build`` splits the corpus into ``shards`` equal row-slices, builds one
    inner engine per shard, and stacks the per-shard device state along a
    leading shard axis that lives on the mesh's ``data`` axis.  ``search``
    runs every shard's engine locally under ``shard_map`` (queries
    replicated), restores global indices from the shard offsets, and merges
    the per-shard top-k lists with the ``core/scan`` running merge.
    Comparisons are summed across shards — the work really done — and a
    per-query ``budget`` is split evenly across shards so the summed count
    respects the same bound as a single-device engine (engine-cfg knobs
    like ``rerank`` remain per shard).
    """

    engine: str
    engine_cls: type
    stacked: Any  # pytree; every leaf (S, ...), placed on the mesh's data axis
    static: dict
    shard_size: int
    n: int
    dctx: Any  # dist.sharding.DistCtx over a ("data",) mesh
    search_defaults: dict = dataclasses.field(default_factory=dict)
    attrs: Any = None  # core/attrs store, columns placed on the data axis
    quant: Any = None  # core/quant store, codes placed on the data axis
    chaos: Any = None  # core/chaos.FaultPlan — per-shard fault injection
    _jitted: dict = dataclasses.field(default_factory=dict, repr=False)

    # ------------------------------------------------------------------ build
    @classmethod
    def registry_build(cls, X, cfg: Optional[Mapping[str, Any]] = None) -> "ShardedIndex":
        cfg = dict(cfg or {})
        engine = cfg.pop("engine", "brute")
        shards = int(cfg.pop("shards", 2))
        mesh = cfg.pop("mesh", None)
        engine_cfg = cfg.pop("engine_cfg", None)
        if engine_cfg is None:
            engine_cfg = cfg  # remaining keys configure the inner engine
        elif cfg:
            raise TypeError(f"sharded: pass engine keys via engine_cfg OR inline, not both: {sorted(cfg)}")
        return cls.build(X, engine=engine, shards=shards, mesh=mesh, engine_cfg=engine_cfg)

    @classmethod
    def build(
        cls, X, *, engine: str = "brute", shards: int = 2, mesh=None,
        engine_cfg: Optional[Mapping[str, Any]] = None,
    ) -> "ShardedIndex":
        from repro.dist.sharding import search_policy

        X = jnp.asarray(X, jnp.float32)
        n = X.shape[0]
        shards = int(shards)
        if shards < 1 or n % shards != 0:
            raise ValueError(f"corpus rows ({n}) must divide evenly into shards ({shards})")
        engine_cls = get_index(engine)
        if not hasattr(engine_cls, "shard_state"):
            raise TypeError(f"engine {engine!r} does not support sharding (no shard_state)")
        if mesh is None:
            from jax.sharding import Mesh

            devs = jax.devices()
            if len(devs) < shards:
                raise RuntimeError(
                    f"need {shards} devices for {shards} shards, have {len(devs)}"
                )
            mesh = Mesh(np.asarray(devs[:shards]), ("data",))
        if mesh.shape.get("data", 1) != shards:
            raise ValueError(f"mesh data axis {mesh.shape} != shards {shards}")
        shard_size = n // shards
        states, statics = [], []
        for s in range(shards):
            # bare `build` resolves to the module-level registry function
            # (the class namespace is not an enclosing scope)
            inner = build(engine, X[s * shard_size : (s + 1) * shard_size], engine_cfg)
            st, stat = inner.shard_state()
            states.append(st)
            statics.append(stat)
        merge = getattr(engine_cls, "merge_shard_static", None)
        static = merge(statics) if merge is not None else default_merge_shard_static(statics)
        from jax.sharding import NamedSharding, PartitionSpec as P

        # place the per-shard state on the data axis ONCE so serving-time
        # searches never re-transfer it
        stacked = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))),
            _stack_shard_states(states),
        )
        return cls(
            engine=engine,
            engine_cls=engine_cls,
            stacked=stacked,
            static=static,
            shard_size=shard_size,
            n=n,
            dctx=search_policy(mesh),
        )

    # -------------------------------------------------------------- attrs
    def attach_attrs(self, store) -> None:
        """Pin the attribute columns on the mesh's data axis: compiled
        predicate masks are then row-sharded alongside the corpus, and the
        per-shard slice reaches each shard's engine with zero reshuffling."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if store.n != self.n:
            raise ValueError(f"attrs cover {store.n} rows != corpus {self.n}")
        store.place(NamedSharding(self.dctx.mesh, P("data")))
        self.attrs = store

    def attach_quant(self, store) -> None:
        """Pin the int8 corpus codes on the mesh's data axis: each shard's
        engine receives its own (shard_size, d) code slice (plus the
        replicated scale vector) with zero reshuffling — the quantized twin
        of ``attach_attrs``.  Only engines whose ``shard_search`` takes a
        ``quant=`` operand can use it; attaching to others would silently
        scan f32, so it raises instead."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if store.rows != self.n:
            raise ValueError(
                f"quant codes cover {store.rows} rows != corpus {self.n}"
            )
        if not getattr(self.engine_cls, "shard_supports_quant", False):
            raise TypeError(
                f"engine {self.engine!r} has no quantized shard scan "
                "(shard_supports_quant)"
            )
        store.place(NamedSharding(self.dctx.mesh, P("data")))
        self.quant = store

    def attach_chaos(self, plan) -> None:
        """Hold the fault plan: ``search`` consults it per call — latency /
        transient rules via the generic ``search`` site, then the ``shard``
        site, raising ``ShardFault`` for any drawn-dead shard the caller
        did not already exclude via ``shard_alive``."""
        self.chaos = plan

    # ----------------------------------------------------------------- search
    def search(self, Q, k: int = 1, *, budget: Optional[int] = None,
               filter=None, shard_alive=None) -> SearchResult:
        """``shard_alive`` — optional per-shard bool sequence: False shards
        are masked out of the merge (their candidates become (-1, +inf) and
        their comparisons 0), the degraded-serving path of DESIGN.md §14.
        The per-query budget split stays S-way, so surviving shards do not
        silently inherit the dead shard's comparison share."""
        from repro.core import filter as filter_lib

        S_total = self.dctx.mesh.shape["data"]
        if shard_alive is not None:
            shard_alive = tuple(bool(a) for a in shard_alive)
            if len(shard_alive) != S_total:
                raise ValueError(
                    f"shard_alive covers {len(shard_alive)} shards, have {S_total}"
                )
            if not any(shard_alive):
                raise ValueError("shard_alive: at least one shard must survive")
        if self.chaos is not None:
            self.chaos.on_search()
            excluded = (set() if shard_alive is None else
                        {i for i, a in enumerate(shard_alive) if not a})
            dead = self.chaos.dead_shards(S_total) - excluded
            dead = {s for s in dead if s < S_total}
            if dead:
                from repro.core import chaos as chaos_lib

                raise chaos_lib.ShardFault(min(dead), n_shards=S_total)

        budget = resolve(budget, self.search_defaults, "budget")
        filter = resolve(filter, self.search_defaults, "filter")
        mask = filter_lib.resolve_mask(filter, self.attrs, self.n)
        S = self.dctx.mesh.shape["data"]
        base = rem = None
        if budget is not None:
            # the budget is per QUERY, not per shard: split it so the summed
            # comparisons stay within the requested bound (floor of 1 per
            # shard — a budget below the shard count degrades to 1 each).
            # The remainder goes to the first ``rem`` shards as a traced
            # per-shard vector so the summed budget is TIGHT, not floored —
            # engines whose budget knob is traceable (infinity's
            # max_comparisons) consume base+1 there; engines with static
            # knobs (IVF's nprobe, NSW's max_steps) resolve from the floor.
            base, rem = divmod(int(budget), S)
            if base == 0:
                base, rem = 1, 0
        Q = jnp.asarray(Q, jnp.float32)
        k = int(k)
        # one compile per knob setting (serving discipline).  Engines whose
        # budget is a traced operand compile ONE program for every budget
        # value (the point of the traced while-gate in vptree) — only the
        # budgeted/unbudgeted distinction stays in their key.
        traced = budget is not None and getattr(
            self.engine_cls, "shard_traced_budget", False
        )
        # engines that size a static knob off the filter's selectivity
        # (infinity's scaled rerank width) get the GLOBAL passing fraction,
        # power-of-two bucketed so it stays a bounded jit-key dimension
        # (cached per predicate: one device sync per distinct filter)
        sel = None
        if mask is not None and getattr(
            self.engine_cls, "shard_uses_selectivity", False
        ):
            sel = filter_lib.bucket_selectivity(
                filter_lib.cached_selectivity(filter, self.attrs, mask))
        key = (k, True if traced else base, mask is not None,
               self.quant is not None, sel, shard_alive)
        fn = self._jitted.get(key)
        if fn is None:
            telem.count("jit_cache_misses_total", engine=self.engine,
                        scope="shard", k=k)
            fn = jax.jit(functools.partial(
                self._search_impl, k=k, budget=base, traced=traced, sel=sel,
                has_mask=mask is not None, has_quant=self.quant is not None,
                shard_alive=shard_alive))
            self._jitted[key] = fn
        else:
            telem.count("jit_cache_hits_total", engine=self.engine,
                        scope="shard", k=k)
        if shard_alive is not None and not all(shard_alive):
            telem.count("shard_masked_total",
                        sum(1 for a in shard_alive if not a),
                        engine=self.engine)
        budget_vec = jnp.full((S,), 0 if base is None else base, jnp.int32)
        if rem:
            budget_vec = budget_vec + (jnp.arange(S, dtype=jnp.int32) < rem)
        args = (self.stacked, Q, budget_vec)
        if mask is not None:
            args = args + (mask,)
        if self.quant is not None:
            codes, scales, sqnorms = self.quant.device_view()
            args = args + (codes, scales, sqnorms)
        # one span covers shard dispatch + per-shard merge: the shard_map
        # body is traced code, so the host boundary is the whole program
        with telem.span("shard_dispatch", engine=self.engine,
                        shards=S_total):
            idx, dist, comps = fn(*args)
            if telem.enabled():
                jax.block_until_ready(comps)
        return SearchResult(idx, dist, comps)

    def _search_impl(self, stacked, Q, budget_vec, *rest, k: int,
                     budget: Optional[int], traced: bool,
                     sel: Optional[float] = None, has_mask: bool = False,
                     has_quant: bool = False, shard_alive=None):
        from jax.sharding import PartitionSpec as P

        cls, static, shard_size = self.engine_cls, self.static, self.shard_size
        traced_budget = traced

        def local(state, Qr, bvec, *rest):
            state = jax.tree_util.tree_map(lambda x: x[0], state)  # drop shard axis
            rest = list(rest)
            extra = {"budget_t": bvec[0]} if traced_budget else {}
            if has_mask:
                # the (shard_size,) row slice of the global mask: the shard's
                # engine ANDs it into its own candidate validity, and local
                # ids stay local — the offset fix below is unchanged
                extra["valid"] = rest.pop(0)
                if sel is not None:
                    extra["sel"] = sel
            if has_quant:
                # (shard_size, d) code slice + replicated scales + the row
                # slice of the precomputed sq-norms: the shard's engine runs
                # its quantized first pass on ITS rows only
                extra["quant"] = (rest.pop(0), rest.pop(0), rest.pop(0))
            idx, dist, comps = cls.shard_search(
                state, Qr, k=k, budget=budget, static=static, **extra
            )
            off = jax.lax.axis_index("data").astype(jnp.int32) * shard_size
            idx = jnp.where(idx >= 0, idx + off, -1)  # local -> global ids
            return idx[None], dist[None], comps[None]

        in_specs = (P("data"), P(), P("data"))
        if has_mask:
            in_specs = in_specs + (P("data"),)
        if has_quant:
            in_specs = in_specs + (P("data"), P(), P("data"))
        fn = jax.shard_map(
            local, mesh=self.dctx.mesh, in_specs=in_specs, out_specs=P("data"),
            check_vma=False,
        )
        args = (stacked, Q, budget_vec) + tuple(rest)
        idx, dist, comps = fn(*args)  # (S, B, k) x2, (S, B)
        if shard_alive is not None and not all(shard_alive):
            # degraded serving: the dead shards' lists become (-1, +inf)
            # no-result slots (merge_topk's padding convention) and their
            # work is not counted — the answer is exactly the merge over
            # the surviving shards' corpora
            alive = jnp.asarray(shard_alive, bool)
            idx = jnp.where(alive[:, None, None], idx, -1)
            dist = jnp.where(alive[:, None, None], dist, jnp.inf)
            comps = jnp.where(alive[:, None], comps, 0)
        # shards are in ascending-offset order, so the running merge keeps
        # the global tie-to-lowest-index contract (DESIGN.md §10)
        mdist, midx = scan_lib.merge_topk(
            jnp.swapaxes(dist, 0, 1), jnp.swapaxes(idx, 0, 1), k=k
        )
        return midx, mdist, jnp.sum(comps, axis=0).astype(jnp.int32)

    def memory_bytes(self) -> int:
        return pytree_nbytes(self.stacked) + side_store_bytes(self)

    # --------------------------------------------------------------- snapshot
    def snapshot_state(self):
        arrays = {
            "stacked": jax.tree_util.tree_map(np.asarray, self.stacked),
        }
        statics = {
            "engine": self.engine,
            "static": self.static,
            "shard_size": self.shard_size,
            "n": self.n,
            "search_defaults": self.search_defaults,
        }
        return arrays, statics

    @classmethod
    def from_snapshot(cls, arrays, statics) -> "ShardedIndex":
        """Re-place the stacked per-shard state on a fresh ("data",) mesh —
        the host must expose at least as many devices as the snapshot had
        shards (same requirement as ``build``)."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.dist.sharding import search_policy

        engine = statics["engine"]
        n, shard_size = int(statics["n"]), int(statics["shard_size"])
        shards = n // shard_size
        devs = jax.devices()
        if len(devs) < shards:
            raise RuntimeError(
                f"snapshot has {shards} shards but only {len(devs)} devices"
            )
        mesh = Mesh(np.asarray(devs[:shards]), ("data",))
        stacked = jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data"))),
            arrays["stacked"],
        )
        inst = cls(
            engine=engine, engine_cls=get_index(engine), stacked=stacked,
            static=dict(statics["static"]), shard_size=shard_size, n=n,
            dctx=search_policy(mesh),
            search_defaults=dict(statics.get("search_defaults") or {}),
        )
        return inst
