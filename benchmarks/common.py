"""Shared benchmark utilities: metrics from the paper (App. F.1) + timing,
plus the provenance stamp every ``experiments/BENCH_*.json`` artifact
carries so the perf trajectory stays reconstructable across PRs."""
from __future__ import annotations

import json
import os
import subprocess
import time

import jax
import jax.numpy as jnp
import numpy as np


def env_stamp() -> dict:
    """Provenance of a benchmark run: git commit, jax version, backend,
    device kind and count.  Two artifacts are only comparable when their
    stamps say they ran on comparable stacks — without this the numbers are
    anonymous."""
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:
        commit = "unknown"
    return {
        "git_commit": commit,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }


def write_stamped(path: str, rows) -> None:
    """The one artifact writer: ``{"meta": env_stamp(), "rows": rows}``.
    Every ``BENCH_*.json`` goes through here so the schema (and the stamp)
    cannot drift between benchmarks.  When ``core/telemetry`` is enabled a
    registry summary (per-stage comparison counters, stage latency
    count/sum/mean, dispatch regimes — DESIGN.md §16) rides along under
    ``meta["telemetry"]``, so every perf artifact carries its own
    breakdown of where the time and comparisons went."""
    meta = env_stamp()
    from repro.core import telemetry as telem

    if telem.enabled():
        meta["telemetry"] = telem.summary()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"meta": meta, "rows": rows}, f, indent=1)


def stage_breakdown(engine: str, repeats: int = 1) -> dict:
    """Per-stage ``{comparisons, ms}`` for ``engine`` from the telemetry
    registry (DESIGN.md §16) — the q-sweep's answer to WHERE higher q
    saves work: traversal vs centroid ranking vs bucket scan vs rerank
    comparisons and milliseconds, averaged over ``repeats`` timed runs.
    Callers ``telem.reset()`` before the timed region so the window is one
    cell's; returns {} when telemetry is disabled."""
    from repro.core import telemetry as telem

    if not telem.enabled():
        return {}
    out: dict = {}

    def slot(stage):
        return out.setdefault(stage, {"comparisons": 0.0, "ms": 0.0})

    for lbl, v in telem.counter_series("comparisons_total"):
        if lbl.get("engine") == engine and "stage" in lbl:
            slot(lbl["stage"])["comparisons"] += v / repeats
    for lbl, rec in telem.histogram_series("stage_seconds"):
        if lbl.get("engine") == engine and "stage" in lbl:
            slot(lbl["stage"])["ms"] += rec["sum"] * 1e3 / repeats
    return {
        stage: {"comparisons": round(v["comparisons"], 1),
                "ms": round(v["ms"], 3)}
        for stage, v in sorted(out.items())
    }


def ground_truth(
    X, Q, *, k: int, metric: str = "euclidean", impl: str = "jnp",
) -> tuple[np.ndarray, np.ndarray]:
    """Exact (idx, dist) oracle for recall/rank-order metrics, streamed
    through ``core/scan.topk_scan`` so ground truth never materializes the
    (B, n) score matrix."""
    from repro.core import scan as scan_lib

    dists, idx = scan_lib.topk_scan(
        jnp.asarray(Q, jnp.float32), jnp.asarray(X, jnp.float32),
        k=k, metric=metric, impl=impl,
    )
    return np.asarray(idx), np.asarray(dists)


def recall_at_k(approx_idx: np.ndarray, true_idx: np.ndarray, k: int) -> float:
    """|approx ∩ true| / k, averaged over queries (Eq. 71)."""
    hits = [
        len(set(map(int, a[:k])) & set(map(int, t[:k]))) / k
        for a, t in zip(approx_idx, true_idx)
    ]
    return float(np.mean(hits))


def rank_order_at_k(approx_idx: np.ndarray, true_idx: np.ndarray, k: int) -> float:
    """Absolute RankOrder@k (Eq. 69): mean |i - pi(x_i)| with pi = position in
    the true ranking (k+1 when missing).  0 = perfect."""
    out = []
    for a, t in zip(approx_idx, true_idx):
        pos = {int(x): i + 1 for i, x in enumerate(t[:k])}
        s = sum(abs((i + 1) - pos.get(int(x), k + 1)) for i, x in enumerate(a[:k]))
        out.append(s / k)
    return float(np.mean(out))


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-time (seconds) with jax block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def row(name: str, seconds: float, derived: str = "") -> str:
    return f"{name},{seconds * 1e6:.1f},{derived}"
