"""InfinitySearch — the paper's end-to-end pipeline (Fig. 18).

Offline (build):
  1. sample a projection subset S of the dataset (the paper trains P*_q on a
     fixed 100K subset and applies Phi inductively; we scale this down),
  2. compute the kNN graph of S and the sparse canonical projection D_q
     (Algorithms 6/7),
  3. fit the embedding operator Phi on (S, D_q)  (Eq. 73),
  4. embed the FULL dataset with Phi and build a VP tree over the embedding
     with the Euclidean metric (whose values now approximate q-distances).

Online (search):
  embed the query batch, search the VP tree — single-path descent for q=inf
  (Theorem 1), budgeted best-first for finite q (Algorithm 2), or the
  level-synchronous BEAM traversal over the flattened/bucketed tree (one
  jitted dispatch per batch, DESIGN.md §15; the default for large batches)
  — and optionally rerank the top-K candidates with the ORIGINAL
  dissimilarity (two-stage search, Appendix F.5).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import embedding as embed_lib
from repro.core import index as index_lib
from repro.core import knn_graph as knn_lib
from repro.core import metrics as metrics_lib
from repro.core import qmetric
from repro.core import quant as quant_lib
from repro.core import scan as scan_lib
from repro.core import telemetry as telem
from repro.core import vptree as vptree_lib
from repro.core.index import SearchResult
from repro.kernels import bestfirst


def _note_comps(engine: str, stage: str, qv: float, comps) -> None:
    """Count a stage's comparisons from its device array: added when the
    counter is read, so the search never waits for it (DESIGN.md §16)."""
    telem.count("comparisons_total", comps, engine=engine, stage=stage,
                q=telem.q_label(qv))


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    q: float = math.inf
    metric: str = "euclidean"  # original dissimilarity
    # sparse projection
    knn_k: int = 16
    num_hops: int = 6  # doubling schedule: paths up to 2^num_hops edges
    extra_links: int = 2  # random long-range edges per node (connectivity)
    proj_sample: int = 2048
    # embedding operator
    embed_dim: int = 32
    hidden: tuple[int, ...] = (256, 256)
    train_steps: int = 2000
    batch_pairs: int = 1024
    lr: float = 1e-3
    alpha_t: float = 0.0
    dropout: float = 0.0
    local_frac: float = 0.5
    stress_weight: str = "sammon"
    # embedding validation (held-out pairs vs the canonical projection):
    # Phi is retrained (fresh seed) up to ``max_retrain`` extra times while
    # its held-out neighbor overlap stays below ``val_target``; the best
    # attempt wins and the metrics land in train_history["validation"]
    val_pairs: int = 1024
    val_target: float = 0.0  # 0 = always accept the first fit (validate only)
    max_retrain: int = 2
    # beam traversal (flattened tree, DESIGN.md §15)
    leaf_size: int = 16
    # misc
    seed: int = 0
    impl: str = "jnp"  # 'pallas' routes pairwise/semiring through kernels/


#: ``mode='auto'`` batch threshold: batches at least this large take the
#: one-dispatch beam traversal; smaller (latency-insensitive) batches keep
#: the budget-exact best-first path, whose traced while-gate the sharded
#: remainder split relies on.
AUTO_BEAM_MIN_BATCH = 64


@index_lib.register_index("infinity")
@dataclasses.dataclass
class InfinityIndex:
    """The paper's pipeline: sparse q-metric projection, learned embedding
    Phi, VP-tree search in embedding space, two-stage original-metric
    rerank."""

    config: IndexConfig
    X: jax.Array  # (n, d) original vectors
    Z: jax.Array  # (n, s) embedded vectors
    phi_params: dict
    tree: vptree_lib.VPTree
    train_history: dict
    search_defaults: dict = dataclasses.field(default_factory=dict)
    #: lazily-built beam state: {"flat": FlatVPTree, "Zf": Z[perm],
    #: "zcodes": (int8 codes of Zf, scales) once a quant store is attached}
    _flat: Optional[dict] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    #: lazily-built layout of (tree, Z) for the best-first kernel on a TPU
    _kview: Optional[bestfirst.View] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    #: the best-first budget is a traced while-loop gate, so ShardedIndex
    #: can hand this engine its exact per-shard share (incl. remainder)
    shard_traced_budget = True
    #: ShardedIndex passes the filter's (bucketed) global selectivity so the
    #: per-shard rerank width scales identically to the single-device path
    shard_uses_selectivity = True

    # ------------------------------------------------------------------ build
    @classmethod
    def registry_build(cls, X, cfg=None) -> "InfinityIndex":
        """Registry entry: cfg is an ``IndexConfig`` or a mapping whose keys
        split into IndexConfig fields and search defaults (mode / budget /
        max_comparisons / rerank)."""
        if isinstance(cfg, IndexConfig):
            return cls.build(X, cfg)
        cfg = dict(cfg or {})
        search_keys = ("mode", "budget", "max_comparisons", "rerank",
                       "beam_width", "bucket_cap")
        sdef = {k: cfg.pop(k) for k in search_keys if k in cfg}
        fields = {f.name for f in dataclasses.fields(IndexConfig)}
        unknown = set(cfg) - fields
        if unknown:
            raise TypeError(f"infinity: unknown cfg keys {sorted(unknown)}")
        idx = cls.build(X, IndexConfig(**cfg))
        idx.search_defaults = sdef
        return idx

    @classmethod
    def build(cls, X: jax.Array, config: IndexConfig = IndexConfig()) -> "InfinityIndex":
        X = jnp.asarray(X, jnp.float32)
        n = X.shape[0]
        rng = np.random.default_rng(config.seed)
        t_start = time.perf_counter()

        # 1) projection subset
        if n > config.proj_sample:
            sub = np.sort(rng.choice(n, size=config.proj_sample, replace=False))
            S = X[jnp.asarray(sub)]
        else:
            S = X

        # 2) sparse canonical projection on the subset.  kNN graphs of
        # clustered data can be disconnected — a handful of random long-range
        # edges per node restores connectivity (NSW-style) so the projection
        # assigns finite q-distances to (nearly) all pairs.
        ns = S.shape[0]
        idx, _ = knn_lib.knn_graph(
            S, k=min(config.knn_k, ns - 1), metric=config.metric,
            impl=config.impl,
        )
        mask = knn_lib.knn_mask(idx, ns)
        if config.extra_links > 0:
            links = jnp.asarray(
                rng.integers(0, ns, size=(ns, config.extra_links)), jnp.int32
            )
            mask = mask | knn_lib.knn_mask(links, ns)
        D = metrics_lib.pairwise(S, S, metric=config.metric, impl=config.impl)
        D = jnp.where(jnp.eye(ns, dtype=bool), 0.0, D)
        Dq = qmetric.sparse_canonical_projection(
            D, mask, config.q, num_hops=config.num_hops, impl=config.impl,
            schedule="doubling",
        )
        jax.block_until_ready(Dq)
        t_proj = time.perf_counter()

        # 3) fit Phi
        ecfg = embed_lib.EmbedConfig(
            in_dim=X.shape[1],
            out_dim=config.embed_dim,
            hidden=config.hidden,
            dropout=config.dropout,
            q=config.q,
            lr=config.lr,
            steps=config.train_steps,
            batch_pairs=config.batch_pairs,
            alpha_t=config.alpha_t,
            seed=config.seed,
            local_frac=config.local_frac,
            weight=config.stress_weight,
        )
        phi_params, history = embed_lib.train_embedding(
            S, Dq, ecfg, knn_idx=idx, log_every=100
        )

        # 3b) validate Phi against the canonical projection on held-out
        # pairs; retrain from a fresh seed while the neighbor overlap misses
        # the configured target, keeping the best attempt (F.3's check that
        # the learned operator actually reproduces the projected geometry)
        val = _phi_validation(phi_params, S, Dq, config)
        attempts = 1
        while (val["nn_overlap10"] < config.val_target
               and attempts <= config.max_retrain):
            ecfg2 = dataclasses.replace(ecfg, seed=config.seed + 1000 * attempts)
            params2, hist2 = embed_lib.train_embedding(
                S, Dq, ecfg2, knn_idx=idx, log_every=100
            )
            val2 = _phi_validation(params2, S, Dq, config)
            if val2["nn_overlap10"] > val["nn_overlap10"]:
                phi_params, history, val = params2, hist2, val2
            attempts += 1
        history["validation"] = dict(val, attempts=attempts)
        t_phi = time.perf_counter()

        # 4) embed the full dataset, build the VP tree in embedding space
        Z = embed_lib.apply(phi_params, X)
        Z_host = np.asarray(Z)
        t_embed = time.perf_counter()
        tree = vptree_lib.build_vptree(Z_host, metric="euclidean", seed=config.seed)
        # seconds per build stage, host clock; _flat_view adds "flatten"
        history["build_s"] = {
            "projection": t_proj - t_start, "phi": t_phi - t_proj,
            "embed": t_embed - t_phi, "tree": time.perf_counter() - t_embed,
        }
        return cls(
            config=config, X=X, Z=Z, phi_params=phi_params, tree=tree,
            train_history=history,
        )

    # ----------------------------------------------------------------- search
    def search(
        self,
        Q: jax.Array,
        k: int = 1,
        *,
        mode: Optional[str] = None,
        max_comparisons: Optional[int] = None,
        rerank: Optional[int] = None,
        budget: Optional[int] = None,
        beam_width: Optional[int] = None,
        bucket_cap: Optional[int] = None,
        filter=None,
    ) -> SearchResult:
        """Returns ``SearchResult``: indices (B, k), distances (B, k) in the
        ORIGINAL metric (ascending), comparisons (B,).

        mode: 'descend' (Theorem-1 single path, k=1 effective),
              'best_first' (Algorithm 2 with the index's q),
              'beam' (level-synchronous traversal of the flattened tree —
              one jitted dispatch per batch, DESIGN.md §15; ``beam_width``/
              ``bucket_cap`` override the budget-derived plan),
              'auto' = descend for q=inf & k==1 & no rerank, beam for
              batches of at least ``AUTO_BEAM_MIN_BATCH`` queries, else
              best_first (whose traced budget gate stays comparison-exact).
        budget: uniform-contract alias for ``max_comparisons`` (the explicit
        kwarg wins when both are given).  The beam consumes it as a PLAN —
        levels x width frontier evaluations plus bucket rows — rather than
        a traced gate, so its counts are bounded by, not equal to, the
        budget.
        rerank: two-stage width K (0 = off). Comparisons count tree visits
        plus reranked candidates (each rerank candidate costs one original-
        metric comparison, matching the paper's accounting in F.5).
        filter: predicate spec / (n,) bool mask.  The tree accepts only
        passing candidates (every visit still counts against the budget),
        descent mode is disabled (a single path may hold no passing point),
        and the two-stage width is scaled by 1/selectivity so recall holds
        on narrow filters (DESIGN.md §12).
        Unset kwargs fall back to the instance's ``search_defaults`` (set by
        the registry from leftover cfg keys).
        """
        from repro.core import filter as filter_lib

        sd = self.search_defaults
        mode = index_lib.resolve(mode, sd, "mode", "auto")
        if max_comparisons is None:
            budget = index_lib.resolve(budget, sd, "budget")
            max_comparisons = budget if budget is not None else (sd or {}).get("max_comparisons")
        rerank = int(index_lib.resolve(rerank, sd, "rerank", 0))
        beam_width = index_lib.resolve(beam_width, sd, "beam_width")
        bucket_cap = index_lib.resolve(bucket_cap, sd, "bucket_cap")
        filter = index_lib.resolve(filter, sd, "filter")
        mask = filter_lib.resolve_mask(
            filter, getattr(self, "attrs", None), self.X.shape[0]
        )
        Q = jnp.asarray(Q, jnp.float32)
        with telem.span("embed", engine="infinity"):
            Zq = embed_lib.apply(self.phi_params, Q)
        K = max(k, rerank)
        if mask is not None and rerank:
            # two-stage under a filter: widen the candidate stage by
            # 1/selectivity (power-of-two bucketed) so the rerank still sees
            # ~rerank passing candidates' worth of tree frontier.  The
            # fraction caches next to the compiled mask, so the hot serving
            # path pays the device sync once per distinct predicate
            sel = filter_lib.bucket_selectivity(filter_lib.cached_selectivity(
                filter, getattr(self, "attrs", None), mask))
            K = filter_lib.scaled_width(K, sel, self.X.shape[0])
        if mask is None and self._use_descend(mode, self.config.q, K):
            with telem.span("traversal", engine="infinity", mode="descend"):
                bi, bd, comps = vptree_lib.descend_infty(
                    self.tree, Zq, X=self.Z, metric="euclidean"
                )
            _note_comps("infinity", "traversal", self.config.q, comps)
            idx = bi[:, None]
        elif self._use_beam(mode, Q.shape[0]):
            if rerank:
                # the beam reaches whole buckets, so widening the two-stage
                # shortlist is nearly free — take at least the quant-rule
                # width (8x-k: the flattened frontier is coarser than a
                # per-node descent, see DESIGN.md §15 on the recall budget)
                K = max(K, quant_lib.shortlist_width(k, self.X.shape[0], mult=8))
            flat, Zf, zc = self._flat_view()
            codes, scales = zc if zc is not None else (None, None)
            with telem.span("traversal", engine="infinity", mode="beam"):
                idx, _, comps, stages = vptree_lib.search_beam(
                    flat, Zq, q=self.config.q, k=K, X=Zf, metric="euclidean",
                    max_comparisons=None if max_comparisons is None
                    else int(max_comparisons),
                    beam_width=beam_width, bucket_cap=bucket_cap, valid=mask,
                    codes=codes, scales=scales, with_stages=True,
                )
            for name, stage_comps in stages.items():
                _note_comps("infinity", name, self.config.q, stage_comps)
        else:
            with telem.span("traversal", engine="infinity", mode="best_first"):
                idx, _, comps = vptree_lib.search_best_first(
                    self.tree, Zq, q=self.config.q, k=K, X=self.Z,
                    metric="euclidean",
                    max_comparisons=max_comparisons, valid=mask,
                    kernel_view=self._kernel_view(),
                )
            _note_comps("infinity", "traversal", self.config.q, comps)
        if rerank and K > k:
            with telem.span("rerank", engine="infinity"):
                idx, dists = self._rerank(Q, idx, k)
                # each reranked candidate costs one original-metric comparison
                comps = comps + K
            telem.count("comparisons_total", int(K) * int(idx.shape[0]),
                        engine="infinity", stage="rerank",
                        q=telem.q_label(self.config.q))
        else:
            # same scan-engine path as the rerank branch: the k survivors are
            # scored in the ORIGINAL metric and returned ascending.  comps
            # keeps counting tree visits only (embedding-space evaluations);
            # the k final scores are reporting, not search work — the
            # paper's accounting, see the SearchResult caveat in core/index.
            with telem.span("rerank", engine="infinity"):
                idx, dists = self._rerank(Q, idx[:, :k], k)
        return SearchResult(idx, dists, comps)

    @staticmethod
    def _use_descend(mode: str, q: float, K: int) -> bool:
        """One mode policy for the instance and shard paths: Theorem-1
        descent when asked for, or automatically at q=inf with a single
        survivor (its prune conditions are complementary only there)."""
        return mode == "descend" or (mode == "auto" and math.isinf(q) and K == 1)

    @staticmethod
    def _use_beam(mode: str, batch: int) -> bool:
        """Beam policy shared with the shard path: explicit 'beam', or
        'auto' once the batch is large enough that one fused dispatch beats
        per-budget while-loop lockstep (small batches keep best-first's
        comparison-exact traced gate)."""
        return mode == "beam" or (mode == "auto" and batch >= AUTO_BEAM_MIN_BATCH)

    def _flat_view(self):
        """The lazily-built beam state: flattened tree, layout-ordered
        embedding rows, and (with a quant store attached) their int8 codes.
        Built on first beam search so snapshots/build cost are unchanged;
        ``refresh`` returns a new instance, which resets it."""
        if self._flat is None:
            t0 = time.perf_counter()
            flat = vptree_lib.flatten_vptree(
                self.tree, leaf_size=self.config.leaf_size,
                Z=np.asarray(self.Z), metric="euclidean",
            )
            object.__setattr__(self, "_flat", {
                "flat": flat, "Zf": self.Z[flat.perm], "zcodes": None,
            })
            self.train_history.setdefault("build_s", {})["flatten"] = (
                time.perf_counter() - t0)
        cache = self._flat
        if getattr(self, "quant", None) is not None and cache["zcodes"] is None:
            # bucket scans read EMBEDDING rows, so they need codes of Zf —
            # the attached store quantizes the ORIGINAL rows for the rerank
            scales = quant_lib.absmax_scales(cache["Zf"], axis=0)
            cache["zcodes"] = (quant_lib.encode(cache["Zf"], scales), scales)
        zc = cache["zcodes"] if getattr(self, "quant", None) is not None else None
        return cache["flat"], cache["Zf"], zc

    def _kernel_view(self) -> Optional[bestfirst.View]:
        """The best-first kernel's layout of (tree, Z), built on first use
        where the kernel runs, else None; ``refresh`` resets it."""
        if self._kview is None and bestfirst.applies(self.Z, "euclidean", None):
            object.__setattr__(self, "_kview", bestfirst.view(
                (self.tree.vantage, self.tree.mu, self.tree.left,
                 self.tree.right), self.Z))
        return self._kview

    def _rerank(self, Q: jax.Array, idx: jax.Array, k: int):
        """Specific search (F.5): original-metric distances to K candidates,
        keep the best k — per-query candidate scoring + selection routed
        through the ``core/scan`` engine (invalid slots masked in the merge).

        With a ``quant`` store attached the two-stage rerank itself goes
        two-stage: the K tree candidates are first scored on int8 codes and
        only a ``quant.shortlist_width``-wide sub-shortlist touches the f32
        rows — at serving widths (K in the hundreds) the rerank's f32 reads
        drop ~4x with the exact final ordering preserved for the top k."""
        k = int(k)
        qs = getattr(self, "quant", None)
        if qs is not None:
            w = quant_lib.shortlist_width(k, self.X.shape[0])
            if idx.shape[1] > w:
                codes, scales, _ = qs.device_view()
                idx = _quant_prefilter(
                    Q, idx, codes, scales, k=w, metric=self.config.metric
                )
        return _scan_rerank(Q, idx, self.X, k=k, metric=self.config.metric)

    def memory_bytes(self) -> int:
        total = index_lib.pytree_nbytes(
            (self.X, self.Z, self.phi_params,
             (self.tree.vantage, self.tree.mu, self.tree.left, self.tree.right))
        ) + index_lib.side_store_bytes(self)
        if self._kview is not None:
            total += index_lib.pytree_nbytes(tuple(self._kview))
        if self._flat is not None:
            flat = self._flat["flat"]
            total += index_lib.pytree_nbytes(
                (flat.mu, flat.child_in, flat.child_out, flat.rad_in,
                 flat.rad_out, flat.centroids, flat.bucket_rows,
                 flat.perm, self._flat["Zf"], self._flat["zcodes"])
            )
        return total

    # -------------------------------------------------------------- sharding
    def shard_state(self):
        sd = self.search_defaults or {}
        flat, Zf, _ = self._flat_view()
        arrays = {
            "X": self.X, "Z": self.Z, "phi": self.phi_params,
            "vantage": self.tree.vantage, "mu": self.tree.mu,
            "left": self.tree.left, "right": self.tree.right,
            # flattened beam state — pad-safe across shards: the stacker's
            # -1 (int) / +inf (float) fills produce phantom nodes no real
            # child pointer reaches and phantom buckets no node points to
            "fmu": flat.mu, "fcin": flat.child_in, "fcout": flat.child_out,
            "frin": flat.rad_in, "frout": flat.rad_out,
            "fcent": flat.centroids,
            "fbuckets": flat.bucket_rows, "fperm": flat.perm, "Zf": Zf,
        }
        static = {
            "q": self.config.q, "metric": self.config.metric,
            "depth": self.tree.depth,
            "flat_depth": flat.depth, "leaf_size": flat.leaf_size,
            "mode": sd.get("mode", "auto"),
            "rerank": int(sd.get("rerank") or 0),
            "budget": sd.get("budget", sd.get("max_comparisons")),
            "beam_width": sd.get("beam_width"),
            "bucket_cap": sd.get("bucket_cap"),
        }
        return arrays, static

    @classmethod
    def merge_shard_static(cls, statics: list[dict]) -> dict:
        """Per-shard trees differ only in their depths — take the max (a
        too-deep fori bound just iterates on an empty frontier / node=-1,
        a no-op)."""
        depth_keys = ("depth", "flat_depth")
        merged = dict(statics[0])
        for key in depth_keys:
            merged[key] = max(s[key] for s in statics)
        for s in statics[1:]:
            rest = {k: v for k, v in s.items() if k not in depth_keys}
            if rest != {k: v for k, v in merged.items() if k not in depth_keys}:
                raise ValueError(f"shard statics disagree: {merged} vs {s}")
        return merged

    @classmethod
    def shard_search(cls, state, Q, *, k, budget, static, budget_t=None,
                     valid=None, sel=None):
        # budget_t: traced per-shard comparison budget (base + remainder
        # share from ShardedIndex) — overrides the static floor when given.
        # valid: the shard's row slice of the global filter mask; sel: the
        # GLOBAL bucketed selectivity (a static — per-shard passing
        # fractions are traced, so the width must come from outside).
        # the STATIC per-shard base share (pre-override) — the beam plans
        # its knobs from this, since a traced value can't size static shapes
        plan_budget = budget if budget is not None else static.get("budget")
        if budget_t is not None:
            budget = budget_t
        elif budget is None:
            budget = static.get("budget")
        rerank = int(static.get("rerank") or 0)
        mode = static.get("mode", "auto")
        tree = vptree_lib.VPTree(
            vantage=state["vantage"], mu=state["mu"], left=state["left"],
            right=state["right"], depth=int(static["depth"]),
        )
        Zq = embed_lib.apply(state["phi"], Q)
        K = max(k, rerank)
        if valid is not None and rerank:
            from repro.core import filter as filter_lib

            K = filter_lib.scaled_width(
                K, 1.0 if sel is None else sel, state["Z"].shape[0]
            )
        # same mode resolution as search(): a cfg that picks descend on one
        # device picks it per shard too
        if valid is None and cls._use_descend(mode, static["q"], K):
            bi, _, comps = vptree_lib.descend_infty(
                tree, Zq, X=state["Z"], metric="euclidean"
            )
            idx = bi[:, None]
        elif cls._use_beam(mode, Q.shape[0]):
            if rerank:
                K = max(K, quant_lib.shortlist_width(
                    k, state["Z"].shape[0], mult=8))
            flat = vptree_lib.FlatVPTree(
                mu=state["fmu"], child_in=state["fcin"],
                child_out=state["fcout"], rad_in=state["frin"],
                rad_out=state["frout"], centroids=state["fcent"],
                bucket_rows=state["fbuckets"],
                perm=state["fperm"], depth=int(static["flat_depth"]),
                leaf_size=int(static["leaf_size"]),
            )
            # the beam's budget is a static PLAN, not a traced gate: the
            # per-shard base share (budget_t's floor) sizes the knobs, so
            # summed comparisons stay within the global budget
            idx, _, comps = vptree_lib.search_beam(
                flat, Zq, q=static["q"], k=K, X=state["Zf"],
                metric="euclidean",
                max_comparisons=None if plan_budget is None
                else int(plan_budget),
                beam_width=static.get("beam_width"),
                bucket_cap=static.get("bucket_cap"), valid=valid,
            )
        else:
            idx, _, comps = vptree_lib.search_best_first(
                tree, Zq, q=static["q"], k=K, X=state["Z"], metric="euclidean",
                max_comparisons=budget, valid=valid,
            )
        if rerank and K > k:
            idx, dists = _scan_rerank(Q, idx, state["X"], k=k, metric=static["metric"])
            comps = comps + K
        else:
            idx, dists = _scan_rerank(Q, idx[:, :k], state["X"], k=k, metric=static["metric"])
        return idx, dists, comps

    # --------------------------------------------------------------- refresh
    def refresh(self, X: jax.Array, *, Z: Optional[jax.Array] = None) -> "InfinityIndex":
        """New index over a mutated corpus WITHOUT retraining Phi.

        The paper's inductive argument: Phi was fit on the projection subset
        and applies to unseen points, so a changed corpus only needs (a) the
        new rows embedded (``Z=None`` embeds everything here; the live
        subsystem passes embeddings it computed at upsert time) and (b) the
        VP tree rebuilt over the new embedding — no gradient steps.  The
        drift cost is quality, not correctness: Phi was fit against the OLD
        subset's q-metric, which a ``full`` compaction re-projects away.
        """
        X = jnp.asarray(X, jnp.float32)
        Z = embed_lib.apply(self.phi_params, X) if Z is None else jnp.asarray(Z)
        tree = vptree_lib.build_vptree(
            np.asarray(Z), metric="euclidean", seed=self.config.seed
        )
        new = InfinityIndex(
            config=self.config, X=X, Z=Z, phi_params=self.phi_params, tree=tree,
            train_history=self.train_history,
        )
        new.search_defaults = dict(self.search_defaults)
        return new

    # -------------------------------------------------------------- snapshot
    def snapshot_state(self):
        arrays = {
            "X": self.X, "Z": self.Z, "phi": self.phi_params,
            "vantage": self.tree.vantage, "mu": self.tree.mu,
            "left": self.tree.left, "right": self.tree.right,
        }
        cfg = dataclasses.asdict(self.config)  # tuples -> lists in JSON
        statics = {
            "config": cfg,
            "depth": self.tree.depth,
            "search_defaults": self.search_defaults,
        }
        return arrays, statics

    @classmethod
    def from_snapshot(cls, arrays, statics) -> "InfinityIndex":
        cfg = dict(statics["config"])
        cfg["hidden"] = tuple(cfg["hidden"])
        tree = vptree_lib.VPTree(
            vantage=jnp.asarray(arrays["vantage"], jnp.int32),
            mu=jnp.asarray(arrays["mu"], jnp.float32),
            left=jnp.asarray(arrays["left"], jnp.int32),
            right=jnp.asarray(arrays["right"], jnp.int32),
            depth=int(statics["depth"]),
        )
        phi = jax.tree_util.tree_map(jnp.asarray, arrays["phi"])
        inst = cls(
            config=IndexConfig(**cfg),
            X=jnp.asarray(arrays["X"], jnp.float32),
            Z=jnp.asarray(arrays["Z"], jnp.float32),
            phi_params=phi, tree=tree,
            train_history={},  # training curves are build telemetry, not state
        )
        inst.search_defaults = dict(statics.get("search_defaults") or {})
        return inst


def _phi_validation(phi_params, S, Dq, config: IndexConfig) -> dict:
    """Held-out check that Phi reproduces the canonical projection's
    geometry: Pearson correlation between embedding distances and the
    projected q-distances on ``val_pairs`` random finite pairs, plus the
    mean top-10 neighbor overlap (embedding vs projection) over up to 64
    anchor points — the metric the retrain loop optimizes, since search
    quality depends on neighbor ORDER, not absolute stress."""
    ZS = np.asarray(embed_lib.apply(phi_params, S))
    Dq = np.asarray(Dq)
    ns = ZS.shape[0]
    rng = np.random.default_rng(config.seed + 17)
    npairs = max(int(config.val_pairs), 1)
    ii = rng.integers(0, ns, size=npairs)
    jj = rng.integers(0, ns, size=npairs)
    keep = (ii != jj) & np.isfinite(Dq[ii, jj])
    ii, jj = ii[keep], jj[keep]
    corr = 0.0
    if ii.size >= 2:
        e = np.sqrt(np.maximum(((ZS[ii] - ZS[jj]) ** 2).sum(-1), 0.0))
        t = Dq[ii, jj]
        if e.std() > 1e-12 and t.std() > 1e-12:
            corr = float(np.corrcoef(e, t)[0, 1])
    anchors = rng.choice(ns, size=min(64, ns), replace=False)
    kk = min(10, ns - 1)
    overlap = 0.0
    for a in anchors:
        row = Dq[a].copy()
        row[a] = np.inf
        row = np.where(np.isfinite(row), row, np.inf)
        true_nn = np.argpartition(row, kk - 1)[:kk]
        erow = np.sqrt(np.maximum(((ZS - ZS[a]) ** 2).sum(-1), 0.0))
        erow[a] = np.inf
        est_nn = np.argpartition(erow, kk - 1)[:kk]
        overlap += len(set(true_nn.tolist()) & set(est_nn.tolist())) / kk
    overlap /= max(len(anchors), 1)
    return {"pair_corr": corr, "nn_overlap10": float(overlap),
            "val_pairs": int(ii.size)}


def _scan_rerank(Q: jax.Array, idx: jax.Array, X: jax.Array, *, k: int, metric: str):
    """Batch original-metric scoring of candidate id lists via ``core/scan``."""
    return jax.vmap(
        lambda q, cand: scan_lib.topk_candidates(q, cand, X, k=k, metric=metric)
    )(Q, idx)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _quant_prefilter(Q, idx, codes, scales, *, k: int, metric: str):
    """Shrink candidate lists on int8 codes: (B, K) ids -> the (B, k) best
    by code-space distance (the quantized stage of the two-stage rerank)."""
    out, _ = jax.vmap(
        lambda q, cand: scan_lib.quant_candidates(
            q, cand, codes, scales, k=k, metric=metric
        )
    )(Q, idx)
    return out
