"""Search telemetry subsystem: counters, histograms, spans (DESIGN.md §16).

One process-wide registry answering the question the flat ``stats()``
dict cannot: *which stage* of a query spent the comparisons and the
milliseconds.  In metric-space search the budget currency is distance
evaluations (the paper's App. F.1 accounting), so the registry is built
around labeled counters — ``comparisons_total{engine=...,stage=...,q=...}``
— next to log-spaced latency histograms.

Three primitives:

* ``Counter`` / ``Gauge`` / ``Histogram`` — labeled metrics held in the
  module ``REGISTRY``.  Histograms use fixed log-spaced latency buckets
  (``LATENCY_BUCKETS_S``) so two runs' distributions are always mergeable.
  Use through the convenience entry points ``count`` / ``set_gauge`` /
  ``observe``, which are no-ops (one branch) while telemetry is disabled.
  ``count`` also takes a device array: its sum is added when the counter
  is read (or once the array is ready), so counting never waits on the
  device.
* ``span(name, **labels)`` — a context manager around one stage of host
  work.  It always opens a ``jax.profiler.TraceAnnotation`` of that name
  and labels while the profiler collects, so the span lands on the
  ``/host:CPU`` plane on the same clock as the device's operations; with
  telemetry enabled it also records the wall time into the
  ``stage_seconds`` histogram (labeled ``stage=name``).  The span closes —
  histogram observed, annotation ended and flagged ``error=True`` — even
  when the body raises.
* ``stage_scope(name)`` — the device half of a stage: a decorator that
  traces a jitted body under ``jax.named_scope(name)``, so the stage's
  name sits in the HLO metadata of its operations.

Global switch: ``enable()`` / ``disable()`` (or env ``REPRO_TELEMETRY=1``).
Disabled, every registry entry point returns after a single flag branch,
and a span without a running profiler costs one check; instrumented
code paths are behavior-identical (bit-exact search ids) either way, and
neither state waits on the device: recording only observes values the
search already computed.

Exposition: ``metrics_text()`` renders the registry in Prometheus text
exposition format (``search_latency_bucket{le=...}``,
``comparisons_total{stage=...}``, ...); ``snapshot()`` returns the same
data as a nested dict (what ``SearchServer.stats()['telemetry']`` and the
``BENCH_*.json`` stamps embed).  Timelines come from the JAX profiler
(``jax.profiler.start_trace``), which holds the spans and the device's
operations together.

Naming note: this module is ``repro.core.telemetry`` and nothing else —
``repro.core.metrics`` is the *dissimilarity* registry (euclidean, cosine,
...), an unrelated namespace.  Do not re-export either under the other's
name.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from typing import Optional

import jax
import numpy as np

__all__ = [
    "LATENCY_BUCKETS_S", "Counter", "Gauge", "Histogram", "Registry",
    "REGISTRY", "enabled", "enable", "disable", "reset",
    "count", "set_gauge", "observe", "span", "stage_scope",
    "counter_series", "histogram_series", "counter_total",
    "snapshot", "summary", "metrics_text", "q_label",
]

#: fixed log-spaced latency buckets (seconds): 100us .. 10s in a
#: 1-2.5-5 decade ladder, +Inf implied.  Fixed — never derived from data —
#: so histograms from any two runs/processes merge bucket-by-bucket.
LATENCY_BUCKETS_S = (
    1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
    1e-1, 2.5e-1, 5e-1, 1.0, 2.5, 5.0, 10.0,
)

_ENABLED = os.environ.get("REPRO_TELEMETRY", "") not in ("", "0", "false")
_LOCK = threading.RLock()
_Annotation = jax.profiler.TraceAnnotation
_collecting = _Annotation.is_enabled  # is a profiler trace being taken


def enabled() -> bool:
    return _ENABLED


def enable(on: bool = True) -> None:
    """Flip the global switch.  Enabling mid-run is safe: metrics simply
    start accumulating from here; nothing retroactive is synthesized."""
    global _ENABLED
    _ENABLED = bool(on)


def disable() -> None:
    enable(False)


def _label_key(labels: dict) -> tuple:
    """Canonical hashable identity of a label set (values stringified)."""
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _label_str(key: tuple) -> str:
    return ",".join(f"{k}={v}" for k, v in key)


class Counter:
    """Monotonic labeled counter.

    ``inc`` takes a number, or a device array whose sum it adds later:
    the array is held until it is ready (checked on each later ``inc``)
    or until the counter is read, so counting never waits on the device."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name, self.help = name, help
        self._vals: dict[tuple, float] = {}
        self._pending: list[tuple] = []  # (label key, device array)

    def inc(self, value=1, **labels) -> None:
        if not _ENABLED:
            return
        key = _label_key(labels)
        with _LOCK:
            if hasattr(value, "is_ready"):
                self._pending.append((key, value))
                self._settle(wait=False)
            else:
                self._vals[key] = self._vals.get(key, 0) + value

    def _settle(self, wait: bool) -> None:
        """Add the sums of the held arrays that are ready (every one with
        ``wait``, as a read needs).  Called under ``_LOCK``."""
        held = []
        for key, arr in self._pending:
            if wait or arr.is_ready():
                self._vals[key] = (self._vals.get(key, 0)
                                   + np.asarray(arr).sum().item())
            else:
                held.append((key, arr))
        self._pending = held

    def series(self) -> list[tuple[dict, float]]:
        with _LOCK:
            self._settle(wait=True)
            return [(dict(k), v) for k, v in sorted(self._vals.items())]

    def total(self, **match) -> float:
        """Sum over every label set containing all of ``match``."""
        m = {k: str(v) for k, v in match.items()}
        with _LOCK:
            self._settle(wait=True)
            return sum(
                v for k, v in self._vals.items()
                if all(dict(k).get(mk) == mv for mk, mv in m.items())
            )

    def _reset(self) -> None:
        self._vals.clear()
        self._pending.clear()


class Gauge(Counter):
    """Labeled last-value gauge (same storage, set instead of add)."""

    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        if not _ENABLED:
            return
        with _LOCK:
            self._vals[_label_key(labels)] = value


class Histogram:
    """Labeled histogram over fixed bucket upper bounds (+Inf implied)."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: tuple = LATENCY_BUCKETS_S):
        self.name, self.help = name, help
        self.buckets = tuple(buckets)
        # per label set: [bucket counts ... , +Inf count], sum, count
        self._vals: dict[tuple, list] = {}

    def observe(self, value: float, **labels) -> None:
        if not _ENABLED:
            return
        key = _label_key(labels)
        with _LOCK:
            rec = self._vals.get(key)
            if rec is None:
                rec = self._vals[key] = [[0] * (len(self.buckets) + 1), 0.0, 0]
            counts, _, _ = rec
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    counts[i] += 1
                    break
            else:
                counts[-1] += 1
            rec[1] += value
            rec[2] += 1

    def series(self) -> list[tuple[dict, dict]]:
        with _LOCK:
            return [
                (dict(k), {"buckets": list(rec[0]), "sum": rec[1],
                           "count": rec[2]})
                for k, rec in sorted(self._vals.items())
            ]

    def _reset(self) -> None:
        self._vals.clear()


class Registry:
    """Name -> metric, with get-or-create accessors (kind-checked)."""

    def __init__(self):
        self._metrics: dict[str, object] = {}

    def _get(self, cls, name: str, help: str, **kw):
        # lock-free fast path: dict reads are atomic in CPython, and a hit
        # of the right kind needs no mutation — this runs per count()/
        # observe() on the serving hot path
        m = self._metrics.get(name)
        if type(m) is cls:
            return m
        with _LOCK:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, **kw)
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {m.kind}"
                )
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: tuple = LATENCY_BUCKETS_S) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    def metrics(self) -> dict:
        with _LOCK:
            return dict(self._metrics)

    def reset(self) -> None:
        # drop metrics entirely (not just their series): a reset registry
        # must be indistinguishable from a fresh one — names re-register on
        # the next write, and no call site caches metric objects
        with _LOCK:
            self._metrics.clear()


REGISTRY = Registry()


# ---------------------------------------------------------------------------
# instrument entry points (registry writes: one branch while disabled)
# ---------------------------------------------------------------------------

def count(name: str, value=1, help: str = "", **labels) -> None:
    """Add ``value`` to ``name{**labels}``: a number, or a device array
    whose sum is added once it is ready (``Counter.inc``)."""
    if not _ENABLED:
        return
    REGISTRY.counter(name, help).inc(value, **labels)


def set_gauge(name: str, value: float, help: str = "", **labels) -> None:
    if not _ENABLED:
        return
    REGISTRY.gauge(name, help).set(value, **labels)


def observe(name: str, value: float, help: str = "", **labels) -> None:
    if not _ENABLED:
        return
    REGISTRY.histogram(name, help).observe(value, **labels)


class span:
    """Time a stage: ``with telemetry.span("dispatch", engine="nsw"): ...``.

    Opens a ``jax.profiler.TraceAnnotation(name, **labels)`` whatever the
    switch says, so a running profiler records the span on its own clock
    (the annotation is made only while the profiler collects: that is
    when it records, so the off path pays one check); with telemetry
    enabled the wall time also goes into ``stage_seconds{stage=name,
    **labels}``.  On exception the span still closes, its annotation
    flagged ``error=True``.  A plain class, not a generator: this sits on
    the per-query serving path."""

    __slots__ = ("name", "labels", "_t0", "_ann")

    def __init__(self, name: str, **labels):
        self.name, self.labels = name, labels

    def __enter__(self):
        self._ann = None
        if _collecting():
            self._ann = _Annotation(self.name, **self.labels)
            self._ann.__enter__()
        self._t0 = time.perf_counter() if _ENABLED else None
        return self

    def __exit__(self, etype, exc, tb):
        if self._ann is not None:
            if etype is not None:
                self._ann.set_metadata(error=True)
            self._ann.__exit__(etype, exc, tb)
        if self._t0 is not None:
            observe("stage_seconds", time.perf_counter() - self._t0,
                    stage=self.name, **self.labels)
        return False


def stage_scope(name: str):
    """Decorator for a function traced under ``jax.jit``: its operations
    carry ``name`` in their HLO metadata (``jax.named_scope``, entered
    afresh on every trace), the device half of the host span of that
    name.  Fixed at trace time: it adds nothing to the compile key."""
    def deco(fn):
        @functools.wraps(fn)
        def traced(*a, **kw):
            with jax.named_scope(name):
                return fn(*a, **kw)
        return traced
    return deco


# ---------------------------------------------------------------------------
# read-side: series access, snapshot tree, Prometheus text
# ---------------------------------------------------------------------------

def counter_series(name: str) -> list[tuple[dict, float]]:
    m = REGISTRY.metrics().get(name)
    return m.series() if isinstance(m, Counter) else []


def histogram_series(name: str) -> list[tuple[dict, dict]]:
    m = REGISTRY.metrics().get(name)
    return m.series() if isinstance(m, Histogram) else []


def counter_total(name: str, **match) -> float:
    m = REGISTRY.metrics().get(name)
    return m.total(**match) if isinstance(m, Counter) else 0.0


def snapshot() -> dict:
    """The registry as a nested dict tree (stats()/BENCH embedding)."""
    out: dict = {"enabled": _ENABLED, "counters": {}, "gauges": {},
                 "histograms": {}}
    for name, m in sorted(REGISTRY.metrics().items()):
        if isinstance(m, Histogram):
            out["histograms"][name] = {
                _label_str(_label_key(lbl)): rec for lbl, rec in m.series()
            }
        elif isinstance(m, Gauge):
            out["gauges"][name] = {
                _label_str(_label_key(lbl)): v for lbl, v in m.series()
            }
        elif isinstance(m, Counter):
            out["counters"][name] = {
                _label_str(_label_key(lbl)): v for lbl, v in m.series()
            }
    return out


def summary() -> dict:
    """Compact snapshot for benchmark stamps: histogram bucket arrays are
    collapsed to count/sum/mean — the breakdown, not the full distribution."""
    snap = snapshot()
    hists = {}
    for name, series in snap["histograms"].items():
        hists[name] = {
            lbl: {"count": rec["count"], "sum": round(rec["sum"], 6),
                  "mean": round(rec["sum"] / rec["count"], 6)
                  if rec["count"] else 0.0}
            for lbl, rec in series.items()
        }
    return {"counters": snap["counters"], "gauges": snap["gauges"],
            "histograms": hists}


def _esc(v: str) -> str:
    return v.replace("\\", r"\\").replace('"', r'\"').replace("\n", r"\n")


def _fmt_labels(lbl: dict, extra: Optional[dict] = None) -> str:
    items = {**lbl, **(extra or {})}
    if not items:
        return ""
    inner = ",".join(f'{k}="{_esc(str(v))}"' for k, v in sorted(items.items()))
    return "{" + inner + "}"


def _fmt_val(v: float) -> str:
    return repr(int(v)) if float(v) == int(v) else repr(float(v))


def metrics_text() -> str:
    """Prometheus text exposition format (version 0.0.4) of the registry.

    Histograms expand to cumulative ``<name>_bucket{le=...}`` series plus
    ``<name>_sum`` / ``<name>_count``; counters/gauges render one line per
    label set.  Served by ``examples/serve_search.py --metrics-port``."""
    lines: list[str] = []
    for name, m in sorted(REGISTRY.metrics().items()):
        if m.help:
            lines.append(f"# HELP {name} {m.help}")
        lines.append(f"# TYPE {name} {m.kind}")
        if isinstance(m, Histogram):
            for lbl, rec in m.series():
                cum = 0
                for ub, c in zip(m.buckets, rec["buckets"]):
                    cum += c
                    lines.append(
                        f"{name}_bucket{_fmt_labels(lbl, {'le': repr(float(ub))})} {cum}"
                    )
                cum += rec["buckets"][-1]
                lines.append(
                    f"{name}_bucket{_fmt_labels(lbl, {'le': '+Inf'})} {cum}"
                )
                lines.append(f"{name}_sum{_fmt_labels(lbl)} {repr(rec['sum'])}")
                lines.append(f"{name}_count{_fmt_labels(lbl)} {rec['count']}")
        else:
            for lbl, v in m.series():
                lines.append(f"{name}{_fmt_labels(lbl)} {_fmt_val(v)}")
    return "\n".join(lines) + "\n"


def reset() -> None:
    """Zero every metric (tests / bench cells)."""
    REGISTRY.reset()


def q_label(q) -> str:
    """Canonical string form of the q knob for labels ('inf', '2.0', ...)."""
    try:
        import math as _math

        return "inf" if _math.isinf(float(q)) else str(float(q))
    except (TypeError, ValueError):
        return str(q)
