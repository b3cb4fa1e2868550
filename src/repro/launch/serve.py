"""Serving driver: the paper's online path (Fig. 18) behind a batch API.

  PYTHONPATH=src python -m repro.launch.serve --engine infinity --n 10000
  PYTHONPATH=src python -m repro.launch.serve --engine ivf_flat --shards 2
  PYTHONPATH=src python -m repro.launch.serve --engine nsw --live \
      --delta-cap 512 --snapshot /tmp/idx

``SearchServer`` is registry-driven: any engine key from ``core/index``
(brute / ivf_flat / ivf_pq / nsw / infinity), optionally sharded over the
host's devices, behind one ``query`` method.  Query batches are padded up to
a fixed bucket size so each (bucket, k) pair compiles exactly once — the
static-shape discipline the TPU serving path needs.

``--live`` wraps the engine in the ``core/live`` subsystem: the server
gains ``upsert`` / ``delete`` / ``compact`` / ``snapshot`` operations, and
``stats()`` reports segment composition (frozen size, delta fill,
tombstones, generation) next to the latency percentiles so operators can
see compaction pressure building.  ``--snapshot PATH`` restores the index
from a ``core/store`` snapshot when one exists there, and writes one after
the run otherwise — restart without rebuild.

Filtered search: build the server with ``attrs={column: per-row values}``
and pass ``filter={...}`` (the ``core/filter`` dict sugar) to ``query`` /
``serve`` — every engine then answers only from predicate-passing rows.
``--filter JSON`` smoke-runs it against demo attribute columns, and
``--list-engines`` prints the registry so operators can discover engines
without reading source.

Quantized serving: ``--quant`` (``SearchServer(quant=True)``) adds the
reserved ``quant`` cfg key — the corpus is mirrored as per-dimension int8
codes (``core/quant``) and the scan engines (brute, ivf_flat, infinity's
rerank, the live delta) read 1 byte/dim on the first pass, exactly
reranking a pow2 shortlist in f32.  ``stats()`` reports the code-store
bytes next to memory/QPS so operators see the bandwidth trade.

Fault-tolerant serving (DESIGN.md §14): ``query(deadline_ms=...)`` runs a
per-request controller — remaining deadline maps to a shrinking comparison
budget (``core/backoff.degraded_budget``'s pow2 ladder, the paper's
anytime knob), transient faults are retried with capped exponential
backoff, and when a shard of a sharded index stays dead the request is
answered from the surviving shards with the failed shard masked out of the
merge.  Every answer is a ``ServedResult`` stamped ``degraded`` /
``shards_answered`` so callers can tell exact from best-effort.  The
server runs a SERVING -> DEGRADED -> RECOVERING health state machine:
``snapshot_dir=`` keeps a sha256-verified last-good snapshot that a failed
engine swap auto-restores, and ``stats()`` surfaces health plus
fault/retry/recovery counters.  ``chaos=`` (``--chaos JSON``) arms a
``core/chaos.FaultPlan`` so all of it can be scripted deterministically;
``--deadline-ms`` drives the degraded path from the CLI.

For LM serving, ``make_prefill_step`` / ``make_decode_step`` in
train/train_step.py are the hardware entry points exercised by the dry-run
(prefill_32k / decode_32k / long_500k cells).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import threading
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backoff as backoff_lib
from repro.core import chaos as chaos_lib
from repro.core import index as index_lib
from repro.core import probes as probes_lib
from repro.core import telemetry as telem
from repro.data import synthetic


def _bucket(n: int, floor: int = 8) -> int:
    """Smallest power-of-two >= n (>= floor) — the padded static batch."""
    from repro.core.scan import pow2ceil

    return max(floor, pow2ceil(n))


class LatencyRing:
    """Bounded per-batch latency window — replaces the unbounded
    ``_lat_s`` list, which grew one float per recorded batch forever under
    sustained traffic.  Percentiles/QPS are computed over the most recent
    ``cap`` batches (the operator's rolling window); lifetime totals live
    in separate counters on the server, so ``stats()['batches']`` keeps
    its every-batch-ever meaning while memory stays flat (tested at 100k
    appends in tests/test_telemetry.py)."""

    def __init__(self, cap: int = 4096):
        self.cap = int(cap)
        self._lat = np.zeros((self.cap,), np.float64)
        self._nq = np.zeros((self.cap,), np.int64)
        self._pos = 0
        self._len = 0

    def append(self, lat_s: float, n_queries: int) -> None:
        self._lat[self._pos] = lat_s
        self._nq[self._pos] = n_queries
        self._pos = (self._pos + 1) % self.cap
        self._len = min(self._len + 1, self.cap)

    def __len__(self) -> int:
        return self._len

    def window(self) -> tuple[np.ndarray, np.ndarray]:
        """(latencies_s, batch_sizes) of the window, oldest-truncated."""
        if self._len < self.cap:
            return self._lat[: self._len], self._nq[: self._len]
        return self._lat, self._nq


class ServedResult(NamedTuple):
    """A ``SearchResult`` plus the serving-layer provenance a caller needs
    to tell an exact answer from a best-effort one (DESIGN.md §14).

    ``degraded`` is True when any shard was masked out of the merge —
    ``idx``/``dist`` then cover only the ``shards_answered`` surviving
    shards' rows.  ``retries`` counts transparent re-attempts this request
    absorbed; ``deadline_met`` is False when the answer returned after its
    deadline had already lapsed (the budget floor bounds how small the
    search can shrink).

    Overload provenance (DESIGN.md §18, set by ``launch/runtime``):
    ``queue_ms`` is the time this request waited in the admission queue
    before its batch dispatched (0 for direct ``query`` calls);
    ``outcome`` distinguishes a computed answer (``"ok"``) from an explicit
    shed — ``"shed_expired"`` (deadline lapsed before compute),
    ``"shed_breaker"`` (circuit breaker open, fast-failed) or
    ``"shed_shutdown"`` (still queued when the runtime stopped).  Shed
    results carry idx=-1 rows and zero comparisons: never a silent
    drop."""

    idx: np.ndarray  # (B, k) int32, -1 = no result
    dist: np.ndarray  # (B, k) f32 ascending
    comparisons: np.ndarray  # (B,) int32
    degraded: bool = False
    shards_answered: int = 1
    shards_total: int = 1
    retries: int = 0
    deadline_met: bool = True
    queue_ms: float = 0.0
    outcome: str = "ok"


@dataclasses.dataclass
class FaultPolicy:
    """The serving controller's knobs (``SearchServer(policy=...)``).

    ``max_retries`` bounds transparent re-attempts per request;
    backoff between them is capped exponential (``core/backoff``).
    ``give_up_frac``: once less than this fraction of the deadline
    remains, a failing shard is masked out instead of retried — the
    request's remaining time goes to computing an answer, not to hoping.
    ``budget_floor`` floors the deadline->budget ladder so even a nearly
    expired request runs a minimal real search."""

    max_retries: int = 2
    backoff_base_s: float = 0.005
    backoff_cap_s: float = 0.05
    give_up_frac: float = 0.25
    budget_floor: int = 8


#: the health state machine's states (DESIGN.md §14): SERVING — full
#: answers; DEGRADED — answering from surviving shards / awaiting repair;
#: RECOVERING — a restore of the last good snapshot is in flight.
HEALTH_STATES = ("SERVING", "DEGRADED", "RECOVERING")


class SearchServer:
    """Build once, answer batched queries — the deployable object.

    engine / shards select any registered index; ``swap`` rebuilds a
    different engine over the same corpus (hot-swap).  ``query`` pads the
    incoming batch to a power-of-two bucket (repeating the last row) and
    slices the answer back, so arbitrary client batch sizes never trigger
    fresh compilation beyond one per bucket.

    Fault tolerance (DESIGN.md §14): ``chaos=`` arms a scripted
    ``core/chaos.FaultPlan`` (or its ``{"seed":..., "rules":[...]}`` dict
    sugar) on the serving index; ``query(deadline_ms=...)`` degrades
    instead of dying — retry with capped backoff, shrink the comparison
    budget as the deadline drains, answer from surviving shards when one
    stays dead — and returns a ``ServedResult`` flagged ``degraded`` /
    ``shards_answered``.  ``snapshot_dir=`` keeps a sha256-verified
    last-good snapshot: a failed ``swap`` auto-restores it (health walks
    SERVING -> DEGRADED -> RECOVERING -> SERVING), and ``stats()`` reports
    ``health`` plus the fault/retry/recovery counters.
    """

    #: serving defaults applied when no cfg is given — the bounded two-stage
    #: operating point (budget/rerank land in the engine's search defaults
    #: where applicable); pass cfg={} to get the engine's own raw defaults.
    DEFAULT_BUDGET = 256
    DEFAULT_RERANK = 96

    def __init__(self, corpus, *, engine: str = "infinity", shards: int = 1,
                 cfg: Optional[dict] = None, live: bool = False,
                 delta_cap: int = 1024, attrs: Optional[dict] = None,
                 quant: bool = False, chaos=None,
                 snapshot_dir: Optional[str] = None,
                 policy: Optional[FaultPolicy] = None,
                 probe=None):
        self.corpus = jnp.asarray(corpus, jnp.float32)
        self.attr_values = dict(attrs) if attrs else None
        self.quant = bool(quant)
        self.chaos = None if chaos is None else chaos_lib.FaultPlan.from_cfg(chaos)
        self.policy = policy or FaultPolicy()
        self.snapshot_dir = snapshot_dir
        # online recall probe (DESIGN.md §17): float rate / dict / ProbeConfig
        self._probe = None if probe is None else probes_lib.RecallProbe(probe)
        self._probe_pending: list = []
        self._probe_raw: list = []
        self._probe_raw_q = 0
        self._probe_key = None
        self._probe_filter = None
        self._init_fault_state()
        self.swap(engine, shards=shards, cfg=cfg, live=live, delta_cap=delta_cap)
        if snapshot_dir is not None:
            self._save_good_snapshot()

    def _init_fault_state(self) -> None:
        self.health = "SERVING"
        self.health_log: list[str] = ["SERVING"]
        self._dead_shards: set[int] = set()
        self._last_good: Optional[str] = None
        self._snap_seq = 0
        # one lock for every cross-thread mutable serving stat: the async
        # runtime (DESIGN.md §18) drives ingress from many worker threads,
        # and a plain dict `+= 1` is a read-modify-write that loses
        # increments under races — counters, health transitions and the
        # latency record all mutate under this RLock (re-entrant: _heal
        # counts faults while walking health)
        self._state_lock = threading.RLock()
        self.fault_counters = {
            "faults": 0, "retries": 0, "degraded_queries": 0,
            "recoveries": 0, "snapshot_restores": 0, "snapshot_corrupt": 0,
            "deadline_misses": 0, "quality_breaches": 0,
        }

    def _count_fault(self, key: str, n: int = 1) -> None:
        """Locked fault-counter increment — the only writer of
        ``fault_counters`` (tested for lost updates under concurrent
        queries in tests/test_runtime.py)."""
        with self._state_lock:
            self.fault_counters[key] += n

    def _set_health(self, state: str) -> None:
        assert state in HEALTH_STATES, state
        with self._state_lock:
            if state != self.health:
                telem.count("health_transitions_total",
                            **{"from": self.health, "to": state})
                self.health = state
                self.health_log.append(state)

    # ---------------------------------------------------------- self-healing
    def _save_good_snapshot(self) -> Optional[str]:
        """Write (and sha256-verify) a rotating last-good snapshot under
        ``snapshot_dir``.  A write the chaos plan corrupted fails
        verification and is discarded — the previous good snapshot stays
        the restore point; one clean retry runs because the plan's draws
        advance per call."""
        if self.snapshot_dir is None:
            return None
        from repro.core import store as store_lib

        for _ in range(2):
            self._snap_seq += 1
            path = os.path.join(self.snapshot_dir, f"snap-{self._snap_seq:04d}")
            try:
                store_lib.save(self.index, path)
                store_lib.verify(path)
            except store_lib.SnapshotCorruption:
                self._count_fault("snapshot_corrupt")
                shutil.rmtree(path, ignore_errors=True)
                continue
            old, self._last_good = self._last_good, path
            if old and old != path:
                shutil.rmtree(old, ignore_errors=True)
            return path
        return self._last_good

    def _heal(self, why: str) -> bool:
        """DEGRADED -> RECOVERING -> SERVING: restore the last good
        snapshot (sha256-verified on load).  Falls back to the in-memory
        index — intact by construction, since every mutation publishes
        atomically — when no verified snapshot exists.  Returns True when
        a snapshot restore happened."""
        from repro.core import store as store_lib

        self._set_health("DEGRADED")
        self._set_health("RECOVERING")
        restored = False
        if self._last_good is not None:
            try:
                self.index = store_lib.load(self._last_good)
                if self.chaos is not None:
                    index_lib.attach_chaos(self.index, self.chaos)
                self._count_fault("snapshot_restores")
                restored = True
            except store_lib.SnapshotCorruption:
                self._count_fault("snapshot_corrupt")
        if restored or getattr(self, "index", None) is not None:
            self._count_fault("recoveries")
            self._set_health("SERVING")
        return restored

    def swap(self, engine: str, *, shards: int = 1, cfg: Optional[dict] = None,
             live: Optional[bool] = None, delta_cap: Optional[int] = None,
             quant: Optional[bool] = None) -> None:
        """(Re)build the serving index over the held corpus.  ``live``/
        ``delta_cap``/``quant`` (and the attribute columns given at
        construction) stick across swaps unless overridden."""
        if getattr(self, "corpus", None) is None:
            raise RuntimeError(
                "this server was restored from a snapshot that carries no "
                "corpus (sharded engine state); build a fresh SearchServer "
                "to swap engines"
            )
        if cfg is None:
            cfg = default_cfg(engine, budget=self.DEFAULT_BUDGET,
                              rerank=self.DEFAULT_RERANK)
        self.live = bool(live) if live is not None else getattr(self, "live", False)
        if quant is not None:
            self.quant = bool(quant)
        else:
            self.quant = getattr(self, "quant", False)
        if delta_cap is not None:
            self.delta_cap = int(delta_cap)
        else:
            self.delta_cap = getattr(self, "delta_cap", 1024)
        t0 = time.perf_counter()
        if shards > 1:
            inner, inner_cfg = "sharded", {
                "engine": engine, "shards": shards, "engine_cfg": dict(cfg or {}),
            }
        else:
            inner, inner_cfg = engine, dict(cfg or {})
        attrs = getattr(self, "attr_values", None)
        try:
            if self.live:
                top_cfg = {"engine": inner, "engine_cfg": inner_cfg,
                           "delta_cap": self.delta_cap}
                if attrs:
                    top_cfg["attrs"] = attrs
                if self.quant:
                    top_cfg["quant"] = True
                if self.chaos is not None:
                    top_cfg["chaos"] = self.chaos
                built = index_lib.build("live", self.corpus, top_cfg)
            else:
                if attrs:
                    inner_cfg = dict(inner_cfg) | {"attrs": attrs}
                if self.quant:
                    inner_cfg = dict(inner_cfg) | {"quant": True}
                if self.chaos is not None:
                    inner_cfg = dict(inner_cfg) | {"chaos": self.chaos}
                built = index_lib.build(inner, self.corpus, inner_cfg)
        except chaos_lib.FaultError:
            self._count_fault("faults")
            self._heal(f"swap({engine!r}) build poisoned")
            raise
        self.index = built
        self.engine = engine
        self.shards = shards
        self._dead_shards.clear()
        self.build_s = time.perf_counter() - t0
        self._lat = LatencyRing()  # bounded per-batch latency window
        self._queries = 0
        self._batches = 0
        self._buckets_seen: set = set()  # (engine, bucket, k) jit-cache keys
        if getattr(self, "_probe", None) is not None:
            # fresh engine, fresh estimate: the window must never mix
            # engines, and a rewound ordinal stream keeps the probe set
            # reproducible per (engine, traffic) pair
            self._probe.reset()
        self._probe_pending = []
        self._probe_raw = []
        self._probe_raw_q = 0
        self._probe_key = None
        self._probe_filter = None

    @classmethod
    def restore(cls, path: str) -> "SearchServer":
        """Rebuild a server from a ``core/store`` snapshot — no index build.

        The corpus is recovered where the index carries it (live indexes
        report their logical view; single-device engines hold X); sharded
        snapshots serve fine but hold no rebuildable corpus, so a later
        ``swap()`` raises instead of building on nothing.
        """
        from repro.core import store as store_lib

        index = store_lib.load(path)
        srv = object.__new__(cls)
        srv.index = index

        def unwrap(idx):
            """(engine label, shard count) through live/sharded wrappers."""
            if idx.registry_name == "sharded":
                return idx.engine, idx.n // idx.shard_size
            return getattr(idx, "registry_name", "?"), 1

        srv.live = index.registry_name == "live"
        srv.quant = getattr(index, "quant", None) is not None
        srv.delta_cap = getattr(index, "delta_cap", 1024)
        if srv.live:
            if index.engine == "sharded":
                srv.engine = index.engine_cfg.get("engine", "sharded")
                srv.shards = int(index.engine_cfg.get("shards", 2))
            else:
                srv.engine, srv.shards = index.engine, 1
            corpus = index.corpus()
        else:
            srv.engine, srv.shards = unwrap(index)
            corpus = getattr(index, "X", None)
        srv.corpus = None if corpus is None else jnp.asarray(corpus, jnp.float32)
        # carry restored attribute columns across future swap() rebuilds
        # (live stores are slot-aligned: gather the alive slots, whose
        # order is exactly corpus()'s logical row order)
        store = getattr(index, "attrs", None)
        srv.attr_values = None
        if store is not None and srv.corpus is not None:
            if srv.live:
                alive = np.where(index.slot_to_logical() >= 0)[0]
                srv.attr_values = store.to_values(alive)
            else:
                srv.attr_values = store.to_values(
                    np.arange(int(srv.corpus.shape[0]))
                )
        srv.build_s = 0.0
        srv._lat = LatencyRing()
        srv._queries = 0
        srv._batches = 0
        srv._buckets_seen = set()
        srv.chaos = None
        srv.policy = FaultPolicy()
        srv.snapshot_dir = None
        srv._probe = None
        srv._probe_pending = []
        srv._probe_raw = []
        srv._probe_raw_q = 0
        srv._probe_key = None
        srv._probe_filter = None
        srv._init_fault_state()
        return srv

    def query(self, batch, k: int = 10, *, budget: Optional[int] = None,
              filter: Optional[dict] = None, record: bool = True,
              deadline_ms: Optional[float] = None) -> ServedResult:
        """Answer one query batch; returns a host-side ``ServedResult``.

        ``filter`` — a ``core/filter`` predicate spec (dict sugar: ``{"shop":
        {"isin": [...]}, "price": {"range": [lo, hi]}}``) evaluated against
        the attribute columns the server was built with; the answer then
        only contains passing rows.  ``record=False`` keeps a warm-up/
        compile call out of the stats() latency record.

        ``deadline_ms`` arms the per-request degradation controller
        (DESIGN.md §14): the comparison budget shrinks with the remaining
        deadline on a pow2 ladder, transient faults retry with capped
        exponential backoff while time allows, and a shard that stays dead
        is masked out of the merge so the survivors still answer — the
        result is then stamped ``degraded`` with ``shards_answered`` <
        ``shards_total``.  Without a deadline the same retry/mask logic
        runs, just without budget shrinking."""
        raw_batch = batch  # pre-device view: the probe buffers from this
        arr = np.asarray(batch, np.float32)
        B = arr.shape[0]
        if B == 0:
            raise ValueError("empty query batch")
        Bp = _bucket(B)
        with telem.span("pad", engine=self.engine, bucket=Bp):
            # pad with copies of the last row: static shapes for jit.  The
            # pad runs in numpy ON PURPOSE — a jnp.concatenate here is
            # itself an XLA program compiled per (B, Bp-B) shape pair, so
            # under the async runtime (whose live batch sizes vary freely,
            # DESIGN.md §18) every previously unseen raw size B paid a
            # ~50ms compile inside the serving path.  Host-side padding
            # keeps the device cache keyed by Bp alone.
            if Bp > B:
                arr = np.concatenate(
                    [arr, np.broadcast_to(arr[-1:], (Bp - B, arr.shape[1]))]
                )
            batch = jnp.asarray(arr)
        # serving-layer jit-cache accounting per (engine, bucket, k): a
        # fresh key means this call pays a compile (the per-knob caches
        # below — ShardedIndex._jitted, the engines' jitted fns — miss too)
        bkey = (self.engine, Bp, int(k))
        with self._state_lock:
            fresh = bkey not in self._buckets_seen
            if fresh:
                self._buckets_seen.add(bkey)
        if fresh:
            telem.count("jit_cache_misses_total", engine=self.engine,
                        scope="server", bucket=Bp)
        else:
            telem.count("jit_cache_hits_total", engine=self.engine,
                        scope="server", bucket=Bp)
        pol = self.policy
        dl = backoff_lib.Deadline(deadline_ms)
        S = max(1, int(self.shards)) if not self.live else 1
        excluded: set[int] = set()
        retries = 0
        t0 = time.perf_counter()
        while True:
            eff_budget = backoff_lib.degraded_budget(
                budget, dl.fraction_left(), floor=pol.budget_floor)
            kw = {"budget": eff_budget, "filter": filter}
            if excluded:
                kw["shard_alive"] = tuple(s not in excluded for s in range(S))
            try:
                # the dispatch span closes (error=True) when a chaos fault
                # escapes the engine — the exception-path guarantee
                # tests/test_telemetry.py pins down
                with telem.span("dispatch", engine=self.engine, bucket=Bp):
                    idx, dist, comps = self.index.search(batch, k=k, **kw)
                    jax.block_until_ready(idx)
                break
            except chaos_lib.ShardFault as e:
                self._count_fault("faults")
                telem.count("faults_total", engine=self.engine, kind="shard")
                known_dead = e.shard in self._dead_shards
                out_of_time = dl.fraction_left() < pol.give_up_frac
                if known_dead or out_of_time or retries >= pol.max_retries:
                    # mask the shard out and answer from the survivors —
                    # the request's remaining time goes to computing an
                    # answer, not to hoping the shard comes back
                    excluded.add(e.shard)
                    if len(excluded) >= S:
                        raise  # every shard down: nothing left to answer from
                    self._dead_shards.add(e.shard)
                    self._set_health("DEGRADED")
                    continue  # immediately, no sleep
                retries += 1
                self._count_fault("retries")
                telem.count("retries_total", engine=self.engine, kind="shard")
                time.sleep(backoff_lib.backoff_s(
                    retries - 1, base_s=pol.backoff_base_s,
                    cap_s=pol.backoff_cap_s))
            except chaos_lib.TransientFault:
                self._count_fault("faults")
                telem.count("faults_total", engine=self.engine,
                            kind="transient")
                if retries >= pol.max_retries or dl.expired():
                    raise  # the plan scripted a fault storm; surface it
                retries += 1
                self._count_fault("retries")
                telem.count("retries_total", engine=self.engine,
                            kind="transient")
                time.sleep(backoff_lib.backoff_s(
                    retries - 1, base_s=pol.backoff_base_s,
                    cap_s=pol.backoff_cap_s))
        if not excluded and self._dead_shards:
            # a full, clean answer proves every shard is back: self-heal
            self._dead_shards.clear()
            self._count_fault("recoveries")
            self._set_health("SERVING")
        degraded = bool(excluded)
        if degraded:
            self._count_fault("degraded_queries")
            telem.count("degraded_total", engine=self.engine)
        deadline_met = not dl.expired()
        if not deadline_met:
            self._count_fault("deadline_misses")
            telem.count("deadline_misses_total", engine=self.engine)
        dt = time.perf_counter() - t0
        if record:
            with self._state_lock:
                self._lat.append(dt, B)
                self._queries += B
                self._batches += 1
            telem.observe("search_latency", dt, engine=self.engine,
                          shards=S)
            telem.count("queries_total", B, engine=self.engine)
            if deadline_ms is not None:
                # remaining fraction of the deadline when the answer landed
                # — the headroom the degradation ladder keys off
                telem.set_gauge("deadline_slack_frac", dl.fraction_left(),
                                engine=self.engine)
        with telem.span("fetch", engine=self.engine, bucket=Bp):
            res = ServedResult(
                np.asarray(idx)[:B], np.asarray(dist)[:B],
                np.asarray(comps)[:B], degraded=degraded,
                shards_answered=S - len(excluded), shards_total=S,
                retries=retries, deadline_met=deadline_met,
            )
        if record and self._probe is not None:
            # observe-only: the answer and its recorded latency are final
            # before the probe sees anything (DESIGN.md §17)
            self._probe_observe(raw_batch, res.idx, k, filter)
        return res

    # -------------------------------------------------- online recall probes
    def _probe_observe(self, batch, served_idx, k, filter) -> None:
        """Shadow path entry (DESIGN.md §17): enqueue this recorded batch
        for deferred sampling.  The per-batch cost must be a list append —
        even one numpy call right after engine work pays ~35us of cold
        caches, which is real p50 tax at 1% sampling.  ``_drain_raw``
        does the actual sampling every few batches (amortizing that
        cold-start), sized so high probe rates still flush as eagerly as
        the synchronous form did.  The enqueued query array is the
        caller's — the server assumes it is not mutated in flight (the
        usual zero-copy serving contract).  Never raises into serving — a
        probe failure is a counted telemetry event, not an outage."""
        probe = self._probe
        try:
            self._probe_raw.append((batch, served_idx, int(k), filter))
            self._probe_raw_q += served_idx.shape[0]
            if (len(self._probe_raw) >= 8
                    or probe.cfg.rate * self._probe_raw_q
                    >= probe.cfg.flush_at):
                self._drain_raw()
        except Exception:
            telem.count("probe_errors_total", engine=self.engine)

    def _drain_raw(self) -> None:
        """Sample + buffer every enqueued batch (FIFO, so query ordinals
        land exactly as synchronous per-batch sampling would), flushing
        ground truth whenever the buffer fills or the view changes.  One
        live generation holds for the whole queue: every mutation drains
        through ``flush_probes`` before touching the corpus."""
        raw, self._probe_raw = self._probe_raw, []
        self._probe_raw_q = 0
        probe = self._probe
        gen = self.index.stats()["generation"] if self.live else None
        for batch, served_idx, k, filter in raw:
            B = served_idx.shape[0]
            pick = probe.sample_indices(B)
            if len(pick):
                # one flush = one ground-truth view: same filter, same live
                # generation, same engine — anything else flushes first
                key = (probes_lib.view_key(filter), gen, self.engine)
                if self._probe_pending and key != self._probe_key:
                    self._flush_probes()
                self._probe_key = key
                self._probe_filter = filter
                # batch is the caller's pre-device array (free when it is
                # already host f32 — a device round trip here costs ~100us
                # per sampled batch)
                Qs = np.asarray(batch, np.float32)[:B][pick]
                kp = min(probe.cfg.k, int(k))
                srv = np.asarray(served_idx)[pick][:, :kp]
                for row_q, row_i in zip(Qs, srv):
                    self._probe_pending.append((row_q, row_i))
            if len(self._probe_pending) >= probe.cfg.flush_at:
                self._flush_probes()

    def flush_probes(self) -> None:
        """Run deferred sampling and pending probe ground truth now.
        ``stats()`` calls this so the quality block is current; mutations
        call it so buffered queries are judged against the corpus that
        answered them."""
        if getattr(self, "_probe", None) is None:
            return
        try:
            if self._probe_raw:
                self._drain_raw()
            if self._probe_pending:
                self._flush_probes()
        except Exception:
            telem.count("probe_errors_total", engine=self.engine)

    def _flush_probes(self) -> None:
        probe = self._probe
        pending, self._probe_pending = self._probe_pending, []
        if not pending:
            return
        corpus, mask, id_map = self._probe_view(self._probe_filter)
        if corpus is None:  # restored sharded snapshot holds no corpus
            telem.count("probe_skipped_total", engine=self.engine)
            return
        t0 = time.perf_counter()
        m = len(pending)
        kp = max(len(row) for _, row in pending)
        # pad the flush to the fixed pow2 bucket: the shadow scan compiles
        # O(log) programs, same static-shape discipline as serving
        Mp = _bucket(m, floor=min(probe.cfg.flush_at, 8))
        Qs = np.stack([q for q, _ in pending])
        if Mp > m:
            Qs = np.concatenate([Qs, np.repeat(Qs[-1:], Mp - m, axis=0)])
        kg = min(kp, int(corpus.shape[0]))
        _, gt_i = self._probe_gt(jnp.asarray(Qs, jnp.float32), corpus,
                                 mask, kg)
        gt_i = np.asarray(gt_i)[:m]
        srv = np.full((m, kp), -1, np.int64)
        for i, (_, row) in enumerate(pending):
            srv[i, : len(row)] = row
        if id_map is not None:  # live answers come in slot ids -> logical
            ok = (srv >= 0) & (srv < len(id_map))
            srv = np.where(
                ok, np.asarray(id_map)[np.clip(srv, 0, len(id_map) - 1)], -1
            )
        hits, trials = probes_lib.count_hits(srv, gt_i)
        probe.observe(hits, trials)
        est = probe.estimate()
        labels = dict(engine=self.engine, q=self._probe_q_label(), k=kp)
        telem.set_gauge("recall_estimate", est["recall"], **labels)
        telem.set_gauge("recall_ci_low", est["lo"], **labels)
        telem.set_gauge("recall_ci_high", est["hi"], **labels)
        telem.count("probe_total", m, engine=self.engine)
        telem.observe("probe_seconds", time.perf_counter() - t0,
                      engine=self.engine)
        trans = probe.update_slo()
        if trans == "breach":
            self._count_fault("quality_breaches")
            telem.count("quality_degraded_total", engine=self.engine)
            self._set_health("DEGRADED")
        elif trans == "recover" and not self._dead_shards \
                and self.health != "SERVING":
            self._count_fault("recoveries")
            self._set_health("SERVING")

    def _probe_gt(self, Qs, corpus, mask, k: int):
        """Compiled ground-truth scan for probe flushes: ``topk_scan``
        jitted once per (k, metric, maskedness) — eager dispatch of the
        blocked scan costs ~10x the compiled program, which would make
        the shadow path anything but a ~``rate`` tax.  jit's own shape
        cache handles the pow2-padded flush sizes (O(log) programs)."""
        from repro.core import scan as scan_lib

        met = self._probe_metric()
        key = (int(k), met, mask is not None)
        cache = getattr(self, "_probe_gt_cache", None)
        if cache is None:
            cache = self._probe_gt_cache = {}
        fn = cache.get(key)
        if fn is None:
            if mask is None:
                fn = jax.jit(lambda Q, Y: scan_lib.topk_scan(
                    Q, Y, k=key[0], metric=met))
            else:
                fn = jax.jit(lambda Q, Y, v: scan_lib.topk_scan(
                    Q, Y, k=key[0], metric=met, valid=v))
            cache[key] = fn
        return fn(Qs, corpus) if mask is None else fn(Qs, corpus, mask)

    def _probe_view(self, filter):
        """(corpus, valid mask, served-id map) for probe ground truth: the
        filter- and tombstone-correct sub-corpus, in the id space the
        engine answers in (DESIGN.md §17).  Live: the alive logical view
        with slot->logical mapping; filtered: the predicate mask ANDed in;
        sharded: global row ids over the held corpus."""
        from repro.core import filter as filter_lib

        if self.live:
            live = self.index
            corpus = jnp.asarray(live.corpus(), jnp.float32)
            s2l = live.slot_to_logical()
            mask = None
            if filter is not None:
                if isinstance(filter, (np.ndarray, jnp.ndarray)):
                    slot_mask = np.asarray(filter, bool)
                else:
                    slot_mask = np.asarray(filter_lib.resolve_mask(
                        filter, getattr(live, "attrs", None), len(s2l)))
                mask = jnp.asarray(slot_mask[: len(s2l)][s2l >= 0])
            return corpus, mask, s2l
        if self.corpus is None:
            return None, None, None
        n = int(self.corpus.shape[0])
        mask = None
        if filter is not None:
            mask = filter_lib.resolve_mask(
                filter, getattr(self.index, "attrs", None), n)
        return self.corpus, mask, None

    def _probe_metric(self) -> str:
        for obj in (self.index, getattr(self.index, "config", None)):
            met = getattr(obj, "metric", None)
            if isinstance(met, str):
                return met
        return "euclidean"

    def _probe_q_label(self) -> str:
        q = getattr(getattr(self.index, "config", None), "q", None)
        return telem.q_label(q) if q is not None else "na"

    # --------------------------------------------------- roofline profiling
    def capture_roofline(self, *, batch: Optional[int] = None, k: int = 10,
                         budget: Optional[int] = None) -> dict:
        """Profile the current engine's batched search program (DESIGN.md
        §17): one jit around ``index.search`` at the serving bucket shape,
        lowered and compiled AOT, pushed through ``core/profile`` — the
        ``roofline_*`` gauges land in the telemetry registry and the JSON
        block is returned for artifacts."""
        from repro.core import profile as profile_lib

        if self.corpus is None:
            raise RuntimeError(
                "no corpus held (restored sharded snapshot): cannot "
                "synthesize a representative batch to profile"
            )
        if batch is None:  # default: the largest bucket this engine served
            seen = [b for (e, b, _) in self._buckets_seen if e == self.engine]
            batch = max(seen) if seen else 64
        n = int(self.corpus.shape[0])
        Qs = self.corpus[np.arange(int(batch)) % n]
        prof = profile_lib.capture_search(
            self.index, Qs, k=k, budget=budget, engine=self.engine,
            labels={"shards": self.shards},
        )
        return {prof.name: prof.as_row()}

    # ------------------------------------------------------------- mutation
    def _live_index(self):
        if not self.live:
            raise TypeError(
                f"server runs a frozen {self.engine!r} index; build with "
                "live=True (--live) for upsert/delete/compact"
            )
        return self.index

    def upsert(self, vectors, ids=None, attrs=None) -> np.ndarray:
        """Insert / replace rows; visible to the next query (no rebuild).
        ``attrs``: per-row attribute values for filtered search.

        Self-heals an (injected) delta-buffer overflow: compaction drains
        the delta, then the write retries once."""
        live = self._live_index()
        self.flush_probes()  # judge buffered queries against pre-write corpus
        try:
            return live.upsert(vectors, ids=ids, attrs=attrs)
        except chaos_lib.DeltaOverflow:
            self._count_fault("faults")
            self.compact()
            out = live.upsert(vectors, ids=ids, attrs=attrs)
            self._count_fault("recoveries")
            return out

    def delete(self, ids) -> int:
        """Tombstone rows; returns how many were newly marked dead."""
        live = self._live_index()
        self.flush_probes()  # judge buffered queries against pre-delete corpus
        return live.delete(ids)

    def compact(self, mode: Optional[str] = None) -> np.ndarray:
        """Force a generation swap; returns the old->new slot remap.

        A compaction the chaos plan kills dies *before* the atomic publish
        (``LiveIndex.compact`` builds the new generation into locals and
        swaps every reference at once), so the old generation keeps serving
        exact answers — health stays SERVING, only the fault is counted."""
        self.flush_probes()  # slot ids remap at compaction: judge first
        try:
            return self._live_index().compact(mode)
        except chaos_lib.CompactFault:
            self._count_fault("faults")
            raise

    def snapshot(self, path: str) -> str:
        """Persist the serving index (any engine) with ``core/store``; the
        written snapshot is sha256-verified before this returns (a chaos
        ``snapshot`` rule corrupting the write surfaces here, not at some
        future restore)."""
        from repro.core import store as store_lib

        out = store_lib.save(self.index, path)
        try:
            store_lib.verify(path)
        except store_lib.SnapshotCorruption:
            self._count_fault("snapshot_corrupt")
            raise
        return out

    # ---------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Operator view: latency percentiles over the rolling window
        (the ``LatencyRing``'s most recent batches; ``queries``/``batches``
        stay lifetime totals), plus segment composition when serving a live
        index — delta fill and deleted fraction are the compaction-pressure
        gauges.  With telemetry enabled a ``telemetry`` tree (the registry
        snapshot, DESIGN.md §16) rides along."""
        with self._state_lock:
            # one consistent snapshot of everything worker threads mutate —
            # counters, health, and the latency window (DESIGN.md §18's
            # thread-safety contract, pinned by tests/test_runtime.py)
            out = {
                "engine": self.engine,
                "shards": self.shards,
                "live": self.live,
                "quant": self.quant,
                "queries": self._queries,
                "batches": self._batches,
                "window_batches": len(self._lat),
                "memory_bytes": self.index.memory_bytes(),
                "build_s": round(self.build_s, 3),
            }
            out["health"] = self.health
            if self._dead_shards:
                out["dead_shards"] = sorted(self._dead_shards)
            if any(self.fault_counters.values()):
                out["faults"] = dict(self.fault_counters)
        if self.chaos is not None:
            out["chaos"] = self.chaos.stats()
        if self._probe is not None:
            self.flush_probes()  # quality block reflects every recorded query
            out["quality"] = self._probe.stats()
        qstore = getattr(self.index, "quant", None)
        if qstore is not None:
            # the bandwidth trade at a glance: int8 code bytes the first
            # pass reads vs the f32 corpus bytes it no longer streams
            out["quant_bytes"] = qstore.memory_bytes()
        if len(self._lat):
            lat_s, nq = self._lat.window()
            lat_ms = lat_s * 1e3
            out.update(
                p50_ms=float(np.percentile(lat_ms, 50)),
                p99_ms=float(np.percentile(lat_ms, 99)),
                qps=float(np.sum(nq) / np.sum(lat_s)),
            )
        if telem.enabled():
            out["telemetry"] = telem.summary()
        if self.live:
            seg = self.index.stats()
            out.update(
                generation=seg["generation"], frozen_size=seg["frozen_size"],
                delta_fill=seg["delta_fill"], delta_cap=seg["delta_cap"],
                tombstones=seg["tombstones"], deleted_frac=seg["deleted_frac"],
                n_alive=seg["n_alive"], compactions=seg["compactions"],
            )
        return out

    def metrics_text(self) -> str:
        """The process-wide telemetry registry in Prometheus text
        exposition format — what ``examples/serve_search.py
        --metrics-port`` serves at ``/metrics`` (DESIGN.md §16)."""
        return telem.metrics_text()

    def serve(self, batches, k: int = 10, *, budget: Optional[int] = None,
              filter: Optional[dict] = None,
              deadline_ms: Optional[float] = None) -> dict:
        """Drain a queue of query batches; returns latency/throughput stats.

        One warm-up query runs per distinct padded bucket so compile time
        never pollutes the latency percentiles.  ``deadline_ms`` applies
        the per-request degradation controller to every batch; the summary
        then reports how many answers were degraded / missed deadline.
        """
        batches = list(batches)
        if not batches:
            raise ValueError("serve() needs at least one query batch")
        # warm-up/compile once per distinct padded bucket (a trailing partial
        # batch lands in a smaller bucket than the full ones).  Warm-up runs
        # without the deadline so a compile stall cannot trip degradation.
        seen = set()
        for qb in batches:
            b = _bucket(len(qb))
            if b not in seen:
                seen.add(b)
                self.query(qb, k=k, budget=budget, filter=filter, record=False)
        lat, comps, n_q = [], [], 0
        n_degraded = n_missed = n_retries = 0
        for qb in batches:
            t0 = time.perf_counter()
            res = self.query(qb, k=k, budget=budget, filter=filter,
                             deadline_ms=deadline_ms)
            lat.append(time.perf_counter() - t0)
            comps.append(float(res.comparisons.mean()))
            n_q += res.idx.shape[0]
            n_degraded += int(res.degraded)
            n_missed += int(not res.deadline_met)
            n_retries += res.retries
        lat_ms = np.asarray(lat) * 1e3
        out = {
            "engine": self.engine,
            "shards": self.shards,
            "k": k,
            "batches": len(batches),
            "queries": n_q,
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p99_ms": float(np.percentile(lat_ms, 99)),
            "qps": float(n_q / np.sum(lat)),
            "mean_comparisons": float(np.mean(comps)),
            "memory_bytes": self.index.memory_bytes(),
            "build_s": round(self.build_s, 3),
        }
        if deadline_ms is not None or n_degraded or n_retries:
            out.update(deadline_ms=deadline_ms, degraded_batches=n_degraded,
                       deadline_misses=n_missed, retries=n_retries,
                       health=self.health)
        return out


def default_cfg(engine: str, *, budget: Optional[int], rerank: Optional[int],
                train_steps: int = 600, proj_sample: int = 1000) -> dict:
    """Engine-appropriate serving defaults from the shared CLI knobs."""
    cfg: dict = {}
    if engine == "infinity":
        cfg.update(q=math.inf, proj_sample=proj_sample, train_steps=train_steps)
        if rerank is not None:
            cfg["rerank"] = rerank
    elif engine == "ivf_pq" and rerank is not None:
        cfg["rerank"] = rerank
    if budget is not None:
        cfg["budget"] = budget
    return cfg


def demo_attrs(n: int, seed: int = 0) -> dict:
    """Deterministic attribute columns for the synthetic serving corpus:
    one categorical (``category``: c0..c7 round-robin) and one numeric
    (``score``: uniform [0, 1)) — what ``--filter`` predicates run against."""
    rng = np.random.default_rng(seed)
    return {
        "category": [f"c{i % 8}" for i in range(n)],
        "score": rng.uniform(0.0, 1.0, size=n).astype(np.float32),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="infinity",
                    help=f"one of {', '.join(k for k in index_lib.BUILTIN if k not in ('sharded', 'live'))}")
    ap.add_argument("--list-engines", action="store_true",
                    help="print every registered engine key with a one-line "
                         "summary, then exit")
    ap.add_argument("--shards", type=int, default=1,
                    help="data-shard the corpus over this many devices")
    ap.add_argument("--budget", type=int, default=256,
                    help="per-query comparison budget (engine-interpreted)")
    ap.add_argument("--rerank", type=int, default=96,
                    help="two-stage rerank width (infinity / ivf_pq)")
    ap.add_argument("--live", action="store_true",
                    help="mutable serving: upsert/delete/compact on top of the engine")
    ap.add_argument("--quant", action="store_true",
                    help="int8 corpus codes: scan engines read 1 byte/dim "
                         "on the first pass and exactly rerank in f32 "
                         "(the reserved 'quant' registry cfg key)")
    ap.add_argument("--delta-cap", type=int, default=1024,
                    help="live delta-buffer capacity (compaction trigger)")
    ap.add_argument("--snapshot", default=None, metavar="PATH",
                    help="restore the index from PATH if present, else save there after the run")
    ap.add_argument("--filter", default=None, metavar="JSON",
                    help="predicate for the smoke run, e.g. "
                         '\'{"category": {"isin": ["c0", "c1"]}, '
                         '"score": {"range": [0.0, 0.5]}}\' — evaluated '
                         "against the demo attribute columns (category "
                         "c0..c7, score uniform [0,1))")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline: the controller shrinks the "
                         "comparison budget as it drains, retries transient "
                         "faults with capped backoff, and masks a dead "
                         "shard out rather than miss (DESIGN.md §14)")
    ap.add_argument("--chaos", default=None, metavar="JSON",
                    help="arm a deterministic core/chaos FaultPlan, e.g. "
                         '\'{"seed": 0, "rules": [{"site": "search", '
                         '"kind": "latency", "rate": 0.1, "ms": 20}]}\' — '
                         "sites: search/shard/build/compact/delta/snapshot")
    ap.add_argument("--probe-rate", type=float, default=0.0,
                    help="shadow this fraction of queries through the "
                         "exact oracle: online recall estimate + Wilson "
                         "interval in stats()['quality'] (DESIGN.md §17)")
    ap.add_argument("--probe-slo", type=float, default=None,
                    help="recall SLO floor: a sustained probe estimate "
                         "below it walks health to DEGRADED")
    ap.add_argument("--n", type=int, default=5000)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    args = ap.parse_args()

    if args.list_engines:
        for name, summary in index_lib.list_engines().items():
            print(f"{name:10s} {summary}")
        return

    from repro.launch.compile_cache import place_compile_cache

    place_compile_cache()
    flt = json.loads(args.filter) if args.filter else None
    X = synthetic.make("manifold", args.n + args.queries, seed=0)
    if args.snapshot and os.path.exists(os.path.join(args.snapshot, "meta.json")):
        server = SearchServer.restore(args.snapshot)
        print(f"restored {server.engine} index from {args.snapshot}")
        if flt and getattr(server.index, "attrs", None) is None:
            # the snapshot was saved without attribute columns: attach the
            # deterministic demo columns when that is well-defined —
            # a frozen single-index whose corpus rows ARE the index rows.
            # A live snapshot's corpus() is the logical (alive) view, not
            # slot-aligned, and a sharded snapshot carries no corpus at
            # all: both must be re-saved with attributes instead.
            if server.corpus is None or server.live:
                raise SystemExit(
                    "--filter needs attribute columns, but this snapshot "
                    "was saved without them and they cannot be rebuilt "
                    "for a live/sharded index; re-save it with --filter"
                )
            n = int(server.corpus.shape[0])
            from repro.core import attrs as attrs_lib

            index_lib.attach_store(
                server.index, attrs_lib.AttributeStore.build(demo_attrs(n), n)
            )
    else:
        server = SearchServer(
            X[: args.n], engine=args.engine, shards=args.shards,
            cfg=default_cfg(args.engine, budget=args.budget, rerank=args.rerank),
            live=args.live, delta_cap=args.delta_cap,
            attrs=demo_attrs(args.n) if flt else None, quant=args.quant,
            chaos=json.loads(args.chaos) if args.chaos else None,
            probe=None if args.probe_rate <= 0 else {
                "rate": args.probe_rate,
                **({"slo_floor": args.probe_slo}
                   if args.probe_slo is not None else {}),
            },
        )
    queries = X[args.n:]
    batches = [queries[i : i + args.batch] for i in range(0, len(queries), args.batch)]
    stats = server.serve(batches, k=args.k, budget=args.budget, filter=flt,
                         deadline_ms=args.deadline_ms)
    print(
        f"engine={stats['engine']} shards={stats['shards']} corpus={args.n} "
        f"build={stats['build_s']}s"
        + (" quant=int8" if args.quant else "")
        + (f" filter={args.filter}" if flt else "")
    )
    print(
        f"  {stats['queries']} queries: p50={stats['p50_ms']:.1f}ms "
        f"p99={stats['p99_ms']:.1f}ms qps={stats['qps']:.0f} "
        f"comps/query={stats['mean_comparisons']:.0f}"
    )
    if args.probe_rate > 0:
        qual = server.stats().get("quality", {})
        print(
            f"  quality: probed={qual.get('probed', 0)}/{qual.get('seen', 0)} "
            f"recall~{qual.get('recall_estimate', 0):.3f} "
            f"[{qual.get('ci_low', 0):.3f}, {qual.get('ci_high', 1):.3f}]"
            + (f" slo_floor={args.probe_slo} breached={qual.get('breached')}"
               if args.probe_slo is not None else "")
        )
    if args.deadline_ms is not None or args.chaos:
        print(
            f"  fault: health={server.health} "
            f"degraded={stats.get('degraded_batches', 0)} "
            f"misses={stats.get('deadline_misses', 0)} "
            f"retries={stats.get('retries', 0)}"
            + (f" injected={server.chaos.stats()['injected']}"
               if server.chaos else "")
        )
    if server.live:
        # mutation demo: a churn burst, then the operator's composition view
        rng = np.random.default_rng(1)
        ins = rng.normal(size=(args.batch, X.shape[1])).astype(np.float32)
        new_ids = server.upsert(ins)
        server.delete(new_ids[: args.batch // 4])
        server.query(queries[: args.batch], k=args.k, budget=args.budget)
        s = server.stats()
        print(
            f"  live: gen={s['generation']} frozen={s['frozen_size']} "
            f"delta={s['delta_fill']}/{s['delta_cap']} "
            f"tombstones={s['tombstones']} alive={s['n_alive']} "
            f"compactions={s['compactions']}"
        )
    if args.snapshot and not os.path.exists(os.path.join(args.snapshot, "meta.json")):
        print(f"snapshot -> {server.snapshot(args.snapshot)}")


if __name__ == "__main__":
    main()
