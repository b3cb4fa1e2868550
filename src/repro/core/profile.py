"""Compiled-program roofline profiles (DESIGN.md §17).

Every hot search path in this repo is one jitted XLA program (the
static-shape discipline: server batch buckets, ``ShardedIndex`` shard
programs, the flattened beam traversal, the quantized scans).  This module
captures the *optimized* HLO of those programs — ``fn.lower(args)
.compile().as_text()`` — and runs it through the loop-aware
``dist/roofline`` accounting, so "N× faster" claims come with a flops /
HBM-bytes / arithmetic-intensity / %-of-roofline number instead of a wall
clock alone.

Two capture surfaces:

* ``capture_jit(name, fn, *args)`` — profile a jitted function directly
  (``core/scan.topk_scan``, a ``ShardedIndex._jitted`` entry, ...).
* ``capture_search(index, Q, ...)`` — wrap any registry engine's whole
  batched ``search`` in one ``jax.jit`` and profile that: the compiled
  program *is* the engine's serving dispatch for that (bucket, k) — the
  beam traversal, centroid ranking, int8 first pass and f32 rerank all
  inlined.  Telemetry is suspended during tracing (engine bodies sync
  comparison counts to host, which tracers cannot).

Predicted time is the per-chip three-term roofline (``max`` of compute /
HBM / collective) against ``dist/roofline.PEAKS``: the peaks of the TPU the
program runs on, or of the target chip (``roofline.TARGET_KIND``) when it
runs on anything else.  Measured time is the median post-warmup dispatch.
``pct_of_peak`` = predicted / measured, the fraction of the hardware
ceiling the program achieves — only when the program ran on the chip the
prediction is for.  A CPU run gets ``pct_of_peak = None`` and no gauge: a
CPU time divided into a TPU prediction measures nothing.

Captured profiles land in a process-wide registry (``profiles()``), as
telemetry gauges (``roofline_*{program=...}``) when telemetry is on, and
as the ``roofline`` block on BENCH_topk / BENCH_serving / BENCH_infinity
rows via ``as_row()``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry as telem
from repro.dist import roofline


@dataclasses.dataclass
class ProgramProfile:
    """One compiled program's roofline accounting."""

    name: str
    labels: dict
    flops: float            # loop-aware dot flops (dist/roofline.hlo_stats)
    hbm_bytes: float        # loop-aware instruction-output bytes
    intensity: float        # flops / byte
    dot_count: int
    t_compute_s: float      # flops / peak bf16 FLOP/s
    t_memory_s: float       # bytes / peak HBM bytes/s
    t_collective_s: float   # collective bytes / peak ICI bytes/s
    t_predicted_s: float    # max of the three terms
    dominant: str           # which term bounds the program
    peak_kind: str = roofline.TARGET_KIND  # device kind the peaks are of
    t_measured_s: Optional[float] = None
    pct_of_peak: Optional[float] = None  # predicted / measured, on that chip

    def as_row(self) -> dict:
        """The JSON block bench rows carry."""
        out = {
            "program": self.name,
            "flops": float(self.flops),
            "hbm_bytes": float(self.hbm_bytes),
            "intensity": round(float(self.intensity), 4),
            "dot_count": int(self.dot_count),
            "t_predicted_s": float(self.t_predicted_s),
            "dominant": self.dominant,
            "peak_kind": self.peak_kind,
        }
        if self.t_measured_s is not None:
            out["t_measured_s"] = float(self.t_measured_s)
        if self.pct_of_peak is not None:
            out["pct_of_peak"] = float(self.pct_of_peak)
        return out


#: process-wide capture registry: (name, sorted label items) -> profile
_PROGRAMS: dict = {}


def _key(name: str, labels: Optional[dict]):
    return (name, tuple(sorted((labels or {}).items())))


def reset() -> None:
    _PROGRAMS.clear()


def profiles(name: Optional[str] = None) -> list[ProgramProfile]:
    """Captured profiles, optionally filtered by program name."""
    return [p for p in _PROGRAMS.values() if name is None or p.name == name]


def export_gauges(prof: ProgramProfile) -> None:
    """Publish one profile as telemetry gauges (no-op when telemetry is
    off) — ``roofline_pct_of_peak`` is what the Prometheus exposition and
    the CI observability smoke assert on."""
    if not telem.enabled():
        return
    labels = {"program": prof.name, **{k: v for k, v in prof.labels.items()}}
    telem.set_gauge("roofline_flops", prof.flops, **labels)
    telem.set_gauge("roofline_hbm_bytes", prof.hbm_bytes, **labels)
    telem.set_gauge("roofline_intensity", prof.intensity, **labels)
    telem.set_gauge("roofline_predicted_s", prof.t_predicted_s, **labels)
    if prof.t_measured_s is not None:
        telem.set_gauge("roofline_measured_s", prof.t_measured_s, **labels)
    if prof.pct_of_peak is not None:
        telem.set_gauge("roofline_pct_of_peak", prof.pct_of_peak, **labels)


def _measure(fn, args, kwargs, iters: int = 3) -> float:
    """Median post-warmup dispatch seconds (block_until_ready)."""
    jax.block_until_ready(fn(*args, **kwargs))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def capture_jit(name: str, fn, *args, labels: Optional[dict] = None,
                measure: bool = True, measured_s: Optional[float] = None,
                force: bool = False, export: bool = True,
                **kwargs) -> ProgramProfile:
    """Profile one jitted function at these (static + array) arguments.

    Lowers and compiles via the AOT path, feeds the optimized HLO text to
    the loop-aware ``dist/roofline`` parsers, and (by default) times the
    live dispatch for the predicted-vs-measured pair.  Re-captures of the
    same (name, labels) return the cached profile unless ``force`` or a
    fresh ``measured_s`` is supplied."""
    key = _key(name, labels)
    cached = _PROGRAMS.get(key)
    if cached is not None and not force and measured_s is None:
        return cached
    was_on = telem.enabled()
    telem.disable()  # traced bodies must not sync counters to host
    try:
        lowered = fn.lower(*args, **kwargs)
        compiled = lowered.compile()
    finally:
        if was_on:
            telem.enable()
    hlo = compiled.as_text()
    stats = roofline.hlo_stats(hlo)
    coll = roofline.parse_collectives(hlo)
    dev = jax.devices()[0]
    chip = roofline.device_peaks(dev)
    on_chip = chip is not None
    kind = dev.device_kind if on_chip else roofline.TARGET_KIND
    chip = chip or roofline.peaks(kind)
    t_compute = stats.flops / chip.bf16_flops
    t_memory = stats.bytes / chip.hbm_bytes_s
    t_coll = coll.total_bytes / chip.ici_bytes_s
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    t_pred = max(terms.values())
    if measured_s is None and measure:
        measured_s = _measure(fn, args, kwargs)
    prof = ProgramProfile(
        name=name, labels=dict(labels or {}),
        flops=stats.flops, hbm_bytes=stats.bytes,
        intensity=stats.flops / max(stats.bytes, 1.0),
        dot_count=stats.dot_count,
        t_compute_s=t_compute, t_memory_s=t_memory, t_collective_s=t_coll,
        t_predicted_s=t_pred, dominant=dominant,
        peak_kind=kind,
        t_measured_s=measured_s,
        pct_of_peak=(t_pred / measured_s) if (measured_s and on_chip) else None,
    )
    _PROGRAMS[key] = prof
    if export:
        export_gauges(prof)
    return prof


def capture_search(index, Q, *, k: int = 10, budget: Optional[int] = None,
                   filter=None, engine: Optional[str] = None,
                   labels: Optional[dict] = None, measure: bool = True,
                   force: bool = False, **search_kw) -> ProgramProfile:
    """Profile a registry engine's whole batched search as ONE program.

    ``jax.jit`` around ``index.search`` traces the engine's entire
    dispatch — for a sharded index that includes the shard_map programs,
    for infinity the beam traversal + rerank, for quantized engines the
    int8 scan — so the profile covers exactly what a serving bucket pays.
    Telemetry is suspended while tracing (engines sync comparison counts
    to host inside ``search``; a tracer cannot be synced) and the gauges
    are exported afterwards."""
    eng = engine or getattr(index, "registry_name", type(index).__name__)
    Qj = jnp.asarray(Q, jnp.float32)
    lbl = {"engine": eng, "batch": int(Qj.shape[0]), "k": int(k),
           **(labels or {})}
    key = _key(f"search:{eng}", lbl)
    cached = _PROGRAMS.get(key)
    if cached is not None and not force:
        return cached

    def run(Qb):
        r = index.search(Qb, k=k, budget=budget, filter=filter, **search_kw)
        return r[0], r[1], r[2]

    fn = jax.jit(run)
    was_on = telem.enabled()
    telem.disable()
    try:
        prof = capture_jit(
            f"search:{eng}", fn, Qj, labels=lbl, measure=measure,
            force=force, export=False,
        )
    finally:
        if was_on:
            telem.enable()
    export_gauges(prof)
    return prof
