"""Live index: mutation on top of any frozen engine (DESIGN.md §11).

The paper's pipeline is build-once (sample, project, fit Phi, freeze a VP
tree — Fig. 18); production corpora mutate.  ``LiveIndex`` makes every
registered engine mutable with the classic segment architecture:

* **frozen segment** — an immutable inner engine (any registry key) built
  over the generation's corpus.  Never touched by upserts.
* **delta buffer** — a fixed-capacity ``(cap, d)`` row buffer holding
  vectors inserted since the last compaction, searched by an exact
  ``core/scan.topk_scan`` over the occupied-and-alive slots (the ``valid``
  mask).  Exact original-metric scoring over a small buffer means inserts
  are visible to the very next query at full recall.
* **tombstone bitmap** — one alive/dead bit per addressable slot (frozen
  rows then delta slots).  Deletes flip a bit; nothing is rebuilt.

``search`` oversamples the frozen engine (k' >= k + frozen tombstones, so
deleted rows can never evict a live answer), re-scores the surviving frozen
candidates in the original metric, scans the delta, and merges the two
lists through ``core/scan.merge_topk`` — frozen slot ids are always lower
than delta slot ids and the frozen list is merged first, so the global
tie-to-lowest-index guarantee of the scan contract is preserved.

**Generation-swap compaction**: when the delta fills or the deleted
fraction crosses a threshold, a new frozen engine is built on host over the
compacted corpus (alive frozen rows, then alive delta rows, in insertion
order) and published atomically — searches in flight keep reading the old
generation object; the swap is a single reference assignment.  For the
``infinity`` engine two modes exist: ``full`` re-projects everything (a
from-scratch build — bit-identical to rebuilding on the compacted corpus),
``refresh`` reuses the frozen Phi, carrying the inductively-embedded delta
rows into the new VP tree without retraining (the paper's own
inductive-application argument: Phi extends to unseen points).

Addressing: slot ids are positional within a generation — frozen rows are
``0..n_frozen-1``, delta slot ``j`` is ``n_frozen + j``.  Only compaction
renumbers, and compaction happens only inside ``upsert`` (delta full, or
the deleted fraction past the threshold) or an explicit ``compact()`` —
``delete`` just flips tombstone bits, so held ids survive it.  ``compact()``
returns the old-slot -> new-slot remap (-1 = deleted), ``upsert`` remaps
the ids it returns through any swap it triggered, ``stats()['generation']``
tells a caller whether its ids are still current, and ``slot_to_logical()``
gives the live view's positions at any time (what recall harnesses compare
against).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import filter as filter_lib
from repro.core import index as index_lib
from repro.core import quant as quant_lib
from repro.core import scan as scan_lib
from repro.core import telemetry as telem
from repro.core.index import SearchResult


_pow2ceil = scan_lib.pow2ceil  # the shared width-bucketing discipline


@dataclasses.dataclass
class _Generation:
    """Everything one search touches, swapped as a unit at compaction.

    ``delta_X`` / ``tomb`` / ``fill`` mutate in place between compactions
    (writes land before the fill bump, so a concurrent reader never sees a
    half-written row); compaction builds a complete replacement and
    publishes it with one reference assignment.
    """

    frozen: Any  # inner Index over the generation corpus
    frozen_X: jax.Array  # (n_frozen, d) original vectors of the frozen rows
    delta_X: np.ndarray  # (cap, d) f32 host buffer, rows [0, fill) occupied
    delta_Z: Optional[np.ndarray]  # (cap, s) inductive Phi embeddings (infinity)
    tomb: np.ndarray  # (n_frozen + cap,) bool — the tombstone bitmap
    fill: int = 0
    gen_id: int = 0
    dead_count: int = 0  # running tombstone count: dead_total() is O(1)
    # device mirrors of the mutable state, rebuilt lazily after a mutation
    # so the hot query path never re-uploads an unchanged delta/bitmap
    _dev: Optional[tuple] = dataclasses.field(default=None, repr=False)

    @property
    def n_frozen(self) -> int:
        return int(self.frozen_X.shape[0])

    @property
    def n_slots(self) -> int:
        return self.n_frozen + self.fill

    def dead_frozen(self) -> int:
        return int(self.tomb[: self.n_frozen].sum())

    def dead_total(self) -> int:
        # the counter, not a bitmap scan: search() checks this per query
        return self.dead_count

    def invalidate(self) -> None:
        self._dev = None

    def device_view(self):
        """(delta_X_dev, tomb_frozen_dev, alive_delta_dev, dead_frozen,
        n_alive_delta), uploaded once per mutation instead of per query."""
        if self._dev is None:
            cap = self.delta_X.shape[0]
            alive_d = (np.arange(cap) < self.fill) & ~self.tomb[
                self.n_frozen : self.n_frozen + cap
            ]
            self._dev = (
                jnp.asarray(self.delta_X),
                jnp.asarray(self.tomb[: self.n_frozen]),
                jnp.asarray(alive_d),
                self.dead_frozen(),
                int(alive_d.sum()),
            )
        return self._dev


@functools.partial(jax.jit, static_argnames=("k", "kd", "kq", "metric"))
def _merge_frozen_delta(
    Q, fidx, frozen_X, tomb_f, delta_X, delta_valid, quant=None,
    *, k, kd, kq=0, metric
):
    """Mask + re-score frozen candidates, scan the delta, merge to top-k.

    ``fidx`` is the frozen engine's oversampled candidate list (its raw
    distances are NOT used).  Candidates whose tombstone bit is set become
    -1 and are re-scored away; the survivors are re-scored in the ORIGINAL
    metric via ``topk_candidates`` so the two lists are comparable for
    every engine (ivf_pq without rerank returns ADC scores; infinity
    returns reranked original-metric scores — re-scoring makes the merge
    metric uniform; like the two-stage rerank in F.5, this reporting
    re-score is not counted as search work).

    ``quant`` — (delta codes (cap, d) int8, scales) from the slot-aligned
    quant store — switches the delta scan to the quantized two-stage: int8
    first pass keeps ``kq`` slots, the exact f32 rerank over ``delta_X``
    keeps ``kd``; the merged answer stays in the original metric.
    """
    n_frozen = frozen_X.shape[0]
    alive = (fidx >= 0) & ~tomb_f[jnp.maximum(fidx, 0)]
    cand = jnp.where(alive, fidx, -1)
    fi, fd = jax.vmap(
        lambda q, c: scan_lib.topk_candidates(q, c, frozen_X, k=k, metric=metric)
    )(Q, cand)

    if quant is None:
        dd, dpos = scan_lib.topk_scan(
            Q, delta_X, k=kd, metric=metric, valid=delta_valid
        )
    else:
        dcodes, scales = quant
        _, dpos1 = scan_lib.topk_scan_quant(
            Q, dcodes, scales, k=kq, metric=metric, valid=delta_valid
        )
        dpos, dd = jax.vmap(
            lambda q, c: scan_lib.topk_candidates(
                q, c, delta_X, k=kd, metric=metric
            )
        )(Q, dpos1)
    di = jnp.where(dpos >= 0, n_frozen + dpos, -1).astype(jnp.int32)
    if kd < k:  # pad the delta list to the frozen list's width
        pad = k - kd
        dd = jnp.pad(dd, ((0, 0), (0, pad)), constant_values=jnp.inf)
        di = jnp.pad(di, ((0, 0), (0, pad)), constant_values=-1)

    # frozen first (lower slot ids) -> merge keeps ties at the lowest id
    mdist, midx = scan_lib.merge_topk(
        jnp.stack([fd, dd], axis=1), jnp.stack([fi, di], axis=1), k=k
    )
    return midx, mdist


@index_lib.register_index("live")
class LiveIndex:
    """Mutable wrapper over any frozen engine: upsert / delete / compact.

    cfg keys (``registry_build``): ``engine`` (inner registry key),
    ``engine_cfg`` (its one-mapping config, reused verbatim at every
    compaction so a compacted index equals a from-scratch build),
    ``delta_cap``, ``compact_deleted_frac``, ``auto_compact``,
    ``compact_mode`` ('full' | 'refresh'), plus ``budget`` as a search
    default.  The original dissimilarity for delta scans / re-scoring is
    read from ``engine_cfg['metric']`` (default 'euclidean') — the metric
    every inner engine scores in.
    """

    registry_name = "live"

    def __init__(
        self, gen: _Generation, *, engine: str, engine_cfg: dict, metric: str,
        delta_cap: int, compact_deleted_frac: float, auto_compact: bool,
        compact_mode: str, search_defaults: Optional[dict] = None,
    ):
        self._gen = gen
        self.engine = engine
        self.engine_cfg = dict(engine_cfg)
        self.metric = metric
        self.delta_cap = int(delta_cap)
        self.compact_deleted_frac = float(compact_deleted_frac)
        self.auto_compact = bool(auto_compact)
        self.compact_mode = compact_mode
        self.compactions = 0
        self.search_defaults = dict(search_defaults or {})
        self.attrs = None  # slot-aligned core/attrs store (attach_attrs)
        self.quant = None  # slot-aligned core/quant store (attach_quant)
        self.chaos = None  # core/chaos.FaultPlan (attach_chaos)

    # ------------------------------------------------------------------ attrs
    def attach_attrs(self, store) -> None:
        """Attach a ``core/attrs`` store, slot-aligned: frozen rows then the
        delta buffer's capacity.  Accepts a corpus-length store (registry
        build: extended with missing-sentinel delta slots) or a full
        slot-capacity store (snapshot restore)."""
        gen = self._gen
        cap = gen.n_frozen + self.delta_cap
        if store.n == gen.n_frozen:
            store = store.take(np.arange(gen.n_frozen), capacity=cap)
        elif store.n != cap:
            raise ValueError(
                f"attrs cover {store.n} rows; need the corpus ({gen.n_frozen}) "
                f"or full slot capacity ({cap})"
            )
        self.attrs = store
        self._attach_frozen_view(gen, store)

    def attach_quant(self, store) -> None:
        """Attach a ``core/quant`` store, slot-aligned like the attribute
        store: frozen rows then the delta buffer's capacity.  Accepts a
        corpus-length store (registry build: zero-padded to slot capacity,
        any already-present delta rows quantized in) or a full slot-capacity
        store (snapshot restore — delta codes already in place).  Upserted
        rows are quantized with the FROZEN generation's scales (the same
        inductive-application argument as Phi; compaction recomputes scales
        from the compacted corpus)."""
        gen = self._gen
        cap = gen.n_frozen + self.delta_cap
        if store.rows == gen.n_frozen:
            store = store.take(np.arange(gen.n_frozen), capacity=cap)
            if gen.fill:
                store.set_rows(gen.n_frozen, gen.delta_X[: gen.fill], gen.fill)
        elif store.rows != cap:
            raise ValueError(
                f"quant codes cover {store.rows} rows; need the corpus "
                f"({gen.n_frozen}) or full slot capacity ({cap})"
            )
        self.quant = store
        self._attach_frozen_quant(gen, store)

    def attach_chaos(self, plan) -> None:
        """Hold the fault plan; the live fault sites are ``search`` (entry),
        ``delta`` (upsert — injected overflow) and ``compact`` (fired just
        before the atomic publish: all rebuild work done, crash before the
        swap — the old generation must keep serving untouched)."""
        self.chaos = plan

    @staticmethod
    def _attach_frozen_quant(gen, store) -> None:
        """Give the frozen engine its own frozen-rows code view, so its
        internal scans run the quantized two-stage (engines without a
        quantized scan path — nsw, ivf_pq — hold the view unused)."""
        index_lib.attach_quant_store(
            gen.frozen, store.take(np.arange(gen.n_frozen))
        )

    @staticmethod
    def _attach_frozen_view(gen, store) -> None:
        """Give the frozen engine its own frozen-rows store view, so
        ``search`` can hand it the PREDICATE instead of a raw mask slice —
        the frozen engine then caches the compiled mask and its selectivity
        itself (no per-query device sync on the hot path).  The view's
        vocabulary snapshot stays correct across delta mutations: a label
        first seen in an upsert exists only in delta slots, so the frozen
        view encoding it to "matches nothing" is exactly right; compaction
        re-attaches a fresh view anyway."""
        index_lib.attach_store(
            gen.frozen, store.take(np.arange(gen.n_frozen))
        )

    # ------------------------------------------------------------------ build
    @classmethod
    def registry_build(cls, X, cfg: Optional[Mapping[str, Any]] = None) -> "LiveIndex":
        cfg = dict(cfg or {})
        engine = cfg.pop("engine", "brute")
        if engine == "live":
            raise TypeError("live: cannot wrap a live index in a live index")
        engine_cfg = cfg.pop("engine_cfg", None)
        kw = {
            k: cfg.pop(k)
            for k in ("delta_cap", "compact_deleted_frac", "auto_compact",
                      "compact_mode")
            if k in cfg
        }
        sdef = {k: cfg.pop(k) for k in ("budget",) if k in cfg}
        if engine_cfg is None:
            engine_cfg = cfg  # remaining keys configure the inner engine
        elif cfg:
            raise TypeError(
                f"live: pass inner-engine keys via engine_cfg OR inline, "
                f"not both: {sorted(cfg)}"
            )
        idx = cls.build(X, engine=engine, engine_cfg=engine_cfg, **kw)
        idx.search_defaults = sdef
        return idx

    @classmethod
    def build(
        cls, X, *, engine: str = "brute",
        engine_cfg: Optional[Mapping[str, Any]] = None, delta_cap: int = 1024,
        compact_deleted_frac: float = 0.25, auto_compact: bool = True,
        compact_mode: str = "full",
    ) -> "LiveIndex":
        if compact_mode not in ("full", "refresh"):
            raise ValueError(f"compact_mode must be 'full' or 'refresh': {compact_mode!r}")
        X = jnp.asarray(X, jnp.float32)
        if X.ndim != 2 or X.shape[0] < 1:
            raise ValueError(f"live: need a non-empty (n, d) corpus, got {X.shape}")
        engine_cfg = dict(engine_cfg or {})
        delta_cap = int(delta_cap)
        if delta_cap < 1:
            raise ValueError(f"delta_cap must be >= 1: {delta_cap}")
        # the original dissimilarity every inner engine scores in — for a
        # sharded wrapper it lives on the inner engine's cfg, one level down
        metric_cfg = engine_cfg
        if engine == "sharded":
            inner = engine_cfg.get("engine_cfg")
            if inner is None:  # sharded's inline form: leftover keys = inner cfg
                inner = {k: v for k, v in engine_cfg.items()
                         if k not in ("engine", "shards", "mesh")}
            metric_cfg = inner
            if delta_cap < int(engine_cfg.get("shards", 2)):
                raise ValueError(
                    "live over sharded: delta_cap must be >= the shard count "
                    "(compaction carries up to shards-1 remainder rows)"
                )
        frozen = index_lib.build(engine, X, engine_cfg)
        gen = _Generation(
            frozen=frozen,
            frozen_X=X,
            delta_X=np.zeros((delta_cap, X.shape[1]), np.float32),
            delta_Z=cls._fresh_delta_Z(frozen, delta_cap),
            tomb=np.zeros((X.shape[0] + delta_cap,), bool),
        )
        return cls(
            gen, engine=engine, engine_cfg=engine_cfg,
            metric=metric_cfg.get("metric", "euclidean"), delta_cap=delta_cap,
            compact_deleted_frac=compact_deleted_frac, auto_compact=auto_compact,
            compact_mode=compact_mode,
        )

    @staticmethod
    def _fresh_delta_Z(frozen, cap: int) -> Optional[np.ndarray]:
        """Infinity engines get a parallel buffer of inductive embeddings:
        Phi applies to unseen points (the paper's inductive argument), so new
        rows are embedded at upsert and carried into refresh compactions."""
        Z = getattr(frozen, "Z", None)
        if Z is None:
            return None
        return np.zeros((cap, Z.shape[1]), np.float32)

    # ---------------------------------------------------------------- mutate
    def upsert(self, X_new, ids=None, attrs=None) -> np.ndarray:
        """Insert rows (optionally replacing existing slots); returns the
        assigned slot ids.

        ``ids`` (same length as ``X_new``): existing slot ids to replace —
        each is tombstoned and its new vector appended (segment-architecture
        update = delete + insert; -1 entries mean plain insert).  When the
        delta cannot hold the batch, compaction runs mid-batch; already-
        assigned ids are remapped through the compaction remap, so the
        returned array is valid in the FINAL generation as a whole.

        ``attrs`` — ``{column: per-row values}`` for the inserted rows,
        written into the slot-aligned attribute store.  Columns left out
        (or the whole mapping, when a store exists) get the missing
        sentinel, so unattributed rows never match a filter.
        """
        X_new = np.asarray(X_new, np.float32)
        if X_new.ndim == 1:
            X_new = X_new[None]
        d = self._gen.delta_X.shape[1]
        if X_new.shape[1] != d:
            raise ValueError(f"upsert dim {X_new.shape[1]} != corpus dim {d}")
        if attrs and self.attrs is None:
            raise TypeError(
                "upsert got attrs but this index has no attribute store: "
                "build with an 'attrs' cfg mapping"
            )
        if self.chaos is not None:
            # injected buffer exhaustion: the whole upsert is rejected
            # BEFORE any tombstone or delta write, so a caller's retry
            # starts from unchanged state
            self.chaos.on_delta()
        if self.attrs is not None:
            # validate BEFORE the destructive steps below: a malformed
            # attrs mapping must not tombstone the replaced ids and must
            # not partially publish a chunked batch
            self.attrs.validate_rows(attrs, X_new.shape[0])
        if ids is not None:
            ids = np.asarray(ids, np.int64)
            if ids.shape[0] != X_new.shape[0]:
                raise ValueError("upsert: ids and X_new length mismatch")
            self.delete(ids[ids >= 0])
        out = np.empty((X_new.shape[0],), np.int64)
        done = 0
        while done < X_new.shape[0]:
            gen = self._gen
            room = self.delta_cap - gen.fill
            if room == 0:
                remap = self.compact()
                # rows inserted before the swap live on under new slot ids
                # (they were just written, hence alive: remap is >= 0)
                out[:done] = remap[out[:done]]
                continue
            take = min(room, X_new.shape[0] - done)
            rows = X_new[done : done + take]
            gen.delta_X[gen.fill : gen.fill + take] = rows
            if gen.delta_Z is not None:
                from repro.core import embedding as embed_lib

                gen.delta_Z[gen.fill : gen.fill + take] = np.asarray(
                    embed_lib.apply(gen.frozen.phi_params, jnp.asarray(rows))
                )
            if self.attrs is not None:
                chunk = None if attrs is None else {
                    c: np.asarray(v)[done : done + take]
                    for c, v in dict(attrs).items()
                }
                self.attrs.set_rows(gen.n_frozen + gen.fill, chunk, take)
            if self.quant is not None:
                # quantize under the frozen scales — visible to the very
                # next query's delta code scan
                self.quant.set_rows(gen.n_frozen + gen.fill, rows, take)
            out[done : done + take] = gen.n_frozen + gen.fill + np.arange(take)
            gen.fill += take  # publish the rows only after they are written
            gen.invalidate()
            done += take
        remap = self._maybe_autocompact()
        if remap is not None:
            out = remap[out]
        return out

    def delete(self, ids) -> int:
        """Tombstone slot ids; returns how many were newly marked dead.
        Unknown / out-of-range ids raise — a delete that silently misses
        would leave phantom rows in the next compaction.

        Deletes NEVER renumber: they only flip tombstone bits, so slot ids
        a caller holds stay valid across any number of deletes.  A deleted
        fraction past the threshold is compacted at the next ``upsert`` (or
        explicit ``compact``) — the operations that already hand back
        remapped ids."""
        gen = self._gen
        ids = np.unique(np.atleast_1d(np.asarray(ids, np.int64)))
        if ids.size and ((ids < 0) | (ids >= gen.n_slots)).any():
            bad = ids[(ids < 0) | (ids >= gen.n_slots)]
            raise KeyError(f"delete: slot ids out of range: {bad[:8].tolist()}")
        newly = int((~gen.tomb[ids]).sum())
        gen.tomb[ids] = True
        gen.dead_count += newly
        gen.invalidate()
        return newly

    def _maybe_autocompact(self) -> Optional[np.ndarray]:
        """Compacts when the deleted fraction crosses the threshold;
        returns the remap when a swap happened (callers holding slot ids
        mid-operation translate them through it)."""
        gen = self._gen
        if not self.auto_compact:
            return None
        dead = gen.dead_total()
        # dead == n_slots: nothing alive to freeze — compaction would raise,
        # but the deletes themselves succeeded; wait for the next insert
        if gen.n_slots and dead < gen.n_slots and dead / gen.n_slots >= self.compact_deleted_frac:
            return self.compact()
        return None

    # --------------------------------------------------------------- compact
    def compact(self, mode: Optional[str] = None) -> np.ndarray:
        """Generation swap: rebuild the frozen engine over the compacted
        corpus and publish it atomically.  Returns the old-slot -> new-slot
        remap (-1 = deleted).

        ``full`` rebuilds through the registry with the original
        ``engine_cfg`` — byte-for-byte the engine a from-scratch build on
        the compacted corpus would produce (seeds live in the cfg).
        ``refresh`` (infinity only; falls back to full elsewhere) keeps the
        frozen Phi: alive frozen embeddings and the inductively-embedded
        delta rows are concatenated and only the VP tree is rebuilt — no
        retraining, the paper's inductive application.

        A ``sharded`` inner engine needs its corpus divisible by the shard
        count: the trailing ``n % shards`` rows are carried into the new
        generation's delta buffer instead of the frozen segment (their slot
        ids are unchanged by the carry — delta slots start at the new
        ``n_frozen``), so compaction never pads with phantom rows and never
        fails on an uneven count.
        """
        with telem.span("compaction", engine=self.engine,
                        mode=mode or self.compact_mode):
            return self._compact_impl(mode)

    def _compact_impl(self, mode: Optional[str]) -> np.ndarray:
        gen = self._gen
        mode = mode or self.compact_mode
        fill = gen.fill  # snapshot: rows appended during the rebuild would
        # belong to the NEXT generation; bounding the copy here keeps the
        # remap consistent with what this compaction actually absorbed
        alive_f = ~gen.tomb[: gen.n_frozen]
        alive_d = ~gen.tomb[gen.n_frozen : gen.n_frozen + fill]
        Xf = np.asarray(gen.frozen_X)
        corpus = np.concatenate([Xf[alive_f], gen.delta_X[:fill][alive_d]], axis=0)
        if corpus.shape[0] < 1:
            raise ValueError("compact: every row is tombstoned; nothing to build on")
        carry = 0
        if self.engine == "sharded":
            shards = int(self.engine_cfg.get("shards", 2))
            carry = corpus.shape[0] % shards
            if corpus.shape[0] - carry < shards:
                raise ValueError(
                    f"compact: {corpus.shape[0]} alive rows cannot fill "
                    f"{shards} shards"
                )
        frozen_part = corpus[: corpus.shape[0] - carry]

        if mode == "refresh" and gen.delta_Z is not None:
            frozen = self._refresh_frozen(gen, alive_f, alive_d, frozen_part, fill)
        else:
            frozen = index_lib.build(
                self.engine, jnp.asarray(frozen_part), self.engine_cfg
            )

        remap = np.full((gen.n_slots,), -1, np.int64)
        alive = np.concatenate([alive_f, alive_d])
        remap[alive] = np.arange(int(alive.sum()))

        # realign the side stores into LOCALS: nothing on self mutates until
        # the single publish below, so a compaction that dies at any point —
        # including an injected ``compact``-site fault — leaves the serving
        # generation AND its slot-aligned stores untouched (DESIGN.md §14)
        new_attrs = new_quant = None
        if self.attrs is not None:
            # alive order == compacted corpus order == new slot order (the
            # carry rows land in delta slots whose ids equal their corpus
            # positions), so one gather realigns the store
            new_attrs = self.attrs.take(
                np.where(alive)[0],
                capacity=frozen_part.shape[0] + self.delta_cap,
            )
            index_lib.attach_store(
                frozen, new_attrs.take(np.arange(frozen_part.shape[0]))
            )
        if self.quant is not None:
            # re-quantize from the compacted corpus (fresh scales — what a
            # from-scratch quantized build would compute), padded back out
            # to the new generation's slot capacity; carry rows sit in
            # delta slots whose positions equal their corpus order
            new_quant = quant_lib.QuantStore.build(corpus).take(
                np.arange(corpus.shape[0]),
                capacity=frozen_part.shape[0] + self.delta_cap,
            )
            index_lib.attach_quant_store(
                frozen, new_quant.take(np.arange(frozen_part.shape[0]))
            )

        new_gen = _Generation(
            frozen=frozen,
            frozen_X=jnp.asarray(frozen_part),
            delta_X=np.zeros((self.delta_cap, corpus.shape[1]), np.float32),
            delta_Z=self._fresh_delta_Z(frozen, self.delta_cap),
            tomb=np.zeros((frozen_part.shape[0] + self.delta_cap,), bool),
            gen_id=gen.gen_id + 1,
        )
        if carry:  # carried rows land in delta slots 0..carry-1, whose slot
            # ids equal their corpus positions — the remap stays positional
            new_gen.delta_X[:carry] = corpus[corpus.shape[0] - carry :]
            new_gen.fill = carry
        if self.chaos is not None:
            # the worst-case crash point: every rebuild cost paid, nothing
            # published — searches in flight and after must keep answering
            # from the old generation bit-identically, and no remap escapes
            self.chaos.on_compact()
        # the atomic publish: generation and realigned stores swap together
        self._gen = new_gen
        if new_attrs is not None:
            self.attrs = new_attrs
        if new_quant is not None:
            self.quant = new_quant
        self.compactions += 1
        telem.count("compactions_total", engine=self.engine)
        return remap

    def _refresh_frozen(self, gen, alive_f, alive_d, corpus, fill):
        """Infinity refresh: carry embeddings instead of retraining Phi."""
        old = gen.frozen
        Z = np.concatenate(
            [np.asarray(old.Z)[alive_f], gen.delta_Z[:fill][alive_d]], axis=0
        )
        return old.refresh(jnp.asarray(corpus), Z=jnp.asarray(Z))

    # ---------------------------------------------------------------- search
    def search(self, Q, k: int = 1, *, budget: Optional[int] = None,
               filter=None) -> SearchResult:
        gen = self._gen  # one read: searches never straddle a generation swap
        if self.chaos is not None:
            self.chaos.on_search()
        budget = index_lib.resolve(budget, self.search_defaults, "budget")
        filter = index_lib.resolve(filter, self.search_defaults, "filter")
        Q = jnp.asarray(Q, jnp.float32)
        k = int(k)
        # slot-aligned mask over the full capacity; composition order is
        # filter ∧ tombstone (∧ the inner engine's own validity) — the
        # tombstone/alive AND happens below, per segment (DESIGN.md §12)
        cap = gen.n_frozen + self.delta_cap
        if isinstance(filter, (np.ndarray, jnp.ndarray)) and \
                filter.shape[0] == gen.n_slots and gen.n_slots < cap:
            # raw masks naturally come slot-count sized; pad the unoccupied
            # delta slots False (they hold no row to pass)
            filter = jnp.concatenate(
                [jnp.asarray(filter, bool),
                 jnp.zeros((cap - gen.n_slots,), bool)]
            )
        mask = filter_lib.resolve_mask(filter, self.attrs, cap)
        # frozen-segment filter: hand PREDICATES down as-is (the frozen
        # engine resolves them against its own store view — compiled mask
        # and selectivity cache there, no per-query slicing or sync); raw
        # masks slice positionally
        if mask is None:
            f_filter = None
        elif not isinstance(filter, (np.ndarray, jnp.ndarray)) and \
                getattr(gen.frozen, "attrs", None) is not None:
            f_filter = filter
        else:
            f_filter = mask[: gen.n_frozen]
        if gen.fill == 0 and gen.dead_total() == 0:
            # clean generation: the live wrapper is transparent, so a
            # compacted index answers bit-identically to its frozen engine
            telem.count("live_scan_total", engine=self.engine,
                        segment="frozen")
            with telem.span("frozen_scan", engine=self.engine, clean=True):
                return gen.frozen.search(Q, k=k, budget=budget,
                                         filter=f_filter)

        delta_X, tomb_f, alive_d, dead_frozen, n_alive_d = gen.device_view()
        # oversample: every frozen tombstone can evict at most one live
        # answer, so k' >= k + dead_frozen keeps exhaustive engines exact.
        # Rounding k' up to a power of two bounds recompilation to
        # O(log n_frozen) distinct widths as deletes accumulate.
        kf = min(gen.n_frozen, _pow2ceil(k + dead_frozen))
        telem.count("live_scan_total", engine=self.engine, segment="frozen")
        with telem.span("frozen_scan", engine=self.engine, oversample=kf):
            fres = gen.frozen.search(Q, k=kf, budget=budget, filter=f_filter)

        kd = min(k, self.delta_cap)
        delta_valid = alive_d if mask is None else (
            alive_d & mask[gen.n_frozen :]
        )
        quant = kq = None
        if self.quant is not None:
            # the delta region of the slot-aligned code buffer: int8 first
            # pass keeps kq slots, the exact f32 rerank keeps kd
            codes, scales, _ = self.quant.device_view()
            quant = (codes[gen.n_frozen :], scales)
            kq = min(self.delta_cap, quant_lib.shortlist_width(kd, self.delta_cap))
        telem.count("live_scan_total", engine=self.engine, segment="delta")
        with telem.span("delta_scan", engine=self.engine, fill=gen.fill):
            midx, mdist = _merge_frozen_delta(
                Q, fres.idx, gen.frozen_X, tomb_f, delta_X, delta_valid, quant,
                k=k, kd=kd, kq=kq or 0, metric=self.metric,
            )
        # frozen work as counted by the engine + one comparison per alive
        # (and passing, under a filter) delta row — the scan really scores
        # each of them (on codes when quantized, plus the kq exact rescores)
        if mask is None:
            comps = fres.comparisons + jnp.int32(n_alive_d)
        else:
            comps = fres.comparisons + jnp.sum(delta_valid).astype(jnp.int32)
        if kq:
            comps = comps + jnp.int32(kq)
        return SearchResult(midx, mdist, comps)

    # ------------------------------------------------------------ inspection
    def corpus(self) -> np.ndarray:
        """The live logical corpus: alive frozen rows then alive delta rows,
        in slot order — exactly what the next compaction will freeze."""
        gen = self._gen
        alive_f = ~gen.tomb[: gen.n_frozen]
        alive_d = ~gen.tomb[gen.n_frozen : gen.n_frozen + gen.fill]
        return np.concatenate(
            [np.asarray(gen.frozen_X)[alive_f], gen.delta_X[: gen.fill][alive_d]],
            axis=0,
        )

    def slot_to_logical(self) -> np.ndarray:
        """Slot id -> position in ``corpus()`` (-1 = tombstoned) — the map
        recall harnesses use to compare live answers against a rebuild."""
        gen = self._gen
        alive = ~gen.tomb[: gen.n_slots]
        out = np.full((gen.n_slots,), -1, np.int64)
        out[alive] = np.arange(int(alive.sum()))
        return out

    def stats(self) -> dict:
        """Segment composition — the operator's compaction-pressure gauge."""
        gen = self._gen
        return {
            "engine": self.engine,
            "generation": gen.gen_id,
            "frozen_size": gen.n_frozen,
            "delta_fill": gen.fill,
            "delta_cap": self.delta_cap,
            "tombstones": gen.dead_total(),
            "deleted_frac": gen.dead_total() / max(1, gen.n_slots),
            "n_alive": gen.n_slots - gen.dead_total(),
            "compactions": self.compactions,
            "attr_columns": list(self.attrs.columns()) if self.attrs else [],
            "quant_bytes": self.quant.memory_bytes() if self.quant else 0,
        }

    def memory_bytes(self) -> int:
        gen = self._gen
        # frozen_X is its own resident copy (post-compaction it is a
        # separate device array from whatever the engine holds; at initial
        # build it may alias — reported capacity, not aliasing)
        extra = index_lib.pytree_nbytes(gen.frozen_X)
        extra += gen.delta_X.nbytes + gen.tomb.nbytes
        if gen.delta_Z is not None:
            extra += gen.delta_Z.nbytes
        return gen.frozen.memory_bytes() + int(extra) + \
            index_lib.side_store_bytes(self)

    # --------------------------------------------------------------- snapshot
    def snapshot_state(self):
        from repro.core import store as store_lib

        gen = self._gen
        fa, fs = store_lib.engine_snapshot_state(gen.frozen)
        arrays = {
            "frozen": fa,
            "frozen_X": np.asarray(gen.frozen_X),
            "delta_X": gen.delta_X[: gen.fill],
            # the bitmap snapshots as actual bits (np.packbits)
            "tomb_bits": np.packbits(gen.tomb),
        }
        if gen.delta_Z is not None:
            arrays["delta_Z"] = gen.delta_Z[: gen.fill]
        statics = {
            "engine": self.engine,
            "engine_cfg": self.engine_cfg,
            "metric": self.metric,
            "delta_cap": self.delta_cap,
            "compact_deleted_frac": self.compact_deleted_frac,
            "auto_compact": self.auto_compact,
            "compact_mode": self.compact_mode,
            "compactions": self.compactions,
            "fill": gen.fill,
            "gen_id": gen.gen_id,
            "tomb_len": int(gen.tomb.shape[0]),
            "frozen_statics": fs,
            "search_defaults": self.search_defaults,
        }
        return arrays, statics

    @classmethod
    def from_snapshot(cls, arrays, statics) -> "LiveIndex":
        from repro.core import store as store_lib

        engine = statics["engine"]
        frozen = store_lib.engine_from_snapshot(
            engine, arrays["frozen"], statics["frozen_statics"]
        )
        frozen_X = jnp.asarray(arrays["frozen_X"], jnp.float32)
        cap = int(statics["delta_cap"])
        fill = int(statics["fill"])
        delta_X = np.zeros((cap, frozen_X.shape[1]), np.float32)
        delta_X[:fill] = np.asarray(arrays["delta_X"], np.float32)
        delta_Z = cls._fresh_delta_Z(frozen, cap)
        if delta_Z is not None and "delta_Z" in arrays:
            delta_Z[:fill] = np.asarray(arrays["delta_Z"], np.float32)
        tomb = np.unpackbits(
            np.asarray(arrays["tomb_bits"], np.uint8), count=statics["tomb_len"]
        ).astype(bool)
        gen = _Generation(
            frozen=frozen, frozen_X=frozen_X, delta_X=delta_X, delta_Z=delta_Z,
            tomb=tomb, fill=fill, gen_id=int(statics["gen_id"]),
            dead_count=int(tomb.sum()),
        )
        idx = cls(
            gen, engine=engine, engine_cfg=dict(statics["engine_cfg"]),
            metric=statics["metric"], delta_cap=cap,
            compact_deleted_frac=statics["compact_deleted_frac"],
            auto_compact=statics["auto_compact"],
            compact_mode=statics["compact_mode"],
            search_defaults=dict(statics.get("search_defaults") or {}),
        )
        idx.compactions = int(statics.get("compactions", 0))
        return idx
