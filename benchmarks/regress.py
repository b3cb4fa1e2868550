"""Bench regression sentinel: the perf trajectory becomes a gate
(DESIGN.md §17).

  PYTHONPATH=src python -m benchmarks.regress --quick
  PYTHONPATH=src python -m benchmarks.regress --check        # stamps only
  PYTHONPATH=src python -m benchmarks.regress --baseline F --fresh F \
      --inject p50_ms=1.2 --inject-match engine=brute        # self-test

Loads the stamped ``experiments/BENCH_*.json`` baselines, runs a fresh
benchmark pass at the *same configuration* (``--quick`` restricts to the
cheap cells), matches rows by their identity columns (everything that is
not a measurement: engine, shards, n, k, ...), and compares with
noise-aware thresholds:

* **Speed normalization.**  Latency/QPS comparisons are normalized by the
  median fresh/baseline latency ratio across ALL matched rows — a
  uniformly slower machine (CI runner vs dev box) shifts every ratio and
  is factored out.  The normalizer is clamped at >= 1 for the hard gate:
  a row that is *absolutely* faster than its baseline is never a
  regression, even when the rest of the suite sped up more (machines
  speed up non-uniformly across program types; demanding proportional
  speedups would gate on hardware, not code).  Rows slower than the
  unclamped suite trend are surfaced as warnings.  With fewer than
  ``MIN_SCALE_SAMPLES`` ratios the scale stays 1.0 (no basis to
  normalize).
* **Relative latency/QPS tolerance** (``--rel-tol``, default 0.15): a row
  regresses when its p50 exceeds ``baseline * max(scale, 1) * (1 + tol)``
  (QPS: falls below ``baseline / max(scale, 1) / (1 + tol)``).  On a
  same-speed machine — and in the exact self-comparison mode CI's
  injection self-test runs — this catches a 20% single-row regression
  deterministically.
* **Absolute recall floor** (``--recall-tol``, default 0.05): recall is
  machine-independent, so the comparison is absolute — fresh recall below
  ``baseline - tol`` regresses regardless of speed.
* **Comparison-count creep** (``--comp-tol``, default 0.25): mean
  comparisons are deterministic given the config; growing past
  ``baseline * (1 + tol)`` regresses.
* **Overload economics** (``--shed-tol``, default 0.2): BENCH_load's
  ``goodput_qps`` / ``knee_qps`` gate like QPS (speed-normalized,
  relative), while ``shed_rate`` is a load *fraction* — machine-
  independent because offered load is expressed as multiples of the
  measured saturation — so it gates absolutely: fresh shedding more than
  ``baseline + tol`` of arrivals regresses.

Writes ``REGRESSIONS.md`` and exits 1 on any regression, 2 on malformed
input.  Unstamped artifacts (the pre-PR-5 bare-list/dict format) are
rejected with a pointer at ``benchmarks/migrate_legacy.py`` — anonymous
numbers cannot gate anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

EXPERIMENTS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "experiments")

#: bench key -> committed artifact (the sentinel's baseline universe)
BASELINES = {
    "topk_kernel": "BENCH_topk.json",
    "serving": "BENCH_serving.json",
    "infinity": "BENCH_infinity.json",
    "load": "BENCH_load.json",
}

#: row keys that are measurements (never identity); nested blocks
#: (stages / validation / roofline) are excluded by being non-scalar
MEASUREMENT_KEYS = {
    "p50_ms", "p99_ms", "qps", "mean_comparisons", "build_s",
    "memory_bytes", "quant_bytes", "t_materialize_s", "t_scan_jnp_s",
    "t_scan_pallas_s", "hbm_read_bytes", "hbm_write_bytes_materialize",
    "hbm_write_bytes_fused", "hbm_write_reduction", "recall@k", "recall@1",
    "deadline_ms", "degraded_batches", "deadline_misses", "retries",
    "health", "window_batches",
    # BENCH_load: everything the open-loop run *measures* — identity is
    # (engine, cell, load_frac, n, k, capacity, max_batch) only
    "offered_qps", "goodput_qps", "shed_rate", "sat_qps", "knee_qps",
    "knee_load_frac", "submitted", "completed", "shed", "rejected", "failed",
    "rejected_breaker", "breaker_trips", "deadline_met_frac",
    "p50_ok_ms", "p99_ok_ms", "duration_s",
}

#: lower-is-better wall-clock metrics (speed-normalized, relative tol)
LATENCY_KEYS = ("p50_ms", "t_materialize_s", "t_scan_jnp_s", "t_scan_pallas_s")
MIN_SCALE_SAMPLES = 3


class UnstampedArtifact(ValueError):
    """A benchmark artifact without provenance cannot be a baseline."""


# ---------------------------------------------------------------- loading

def load_stamped(path: str) -> tuple[dict, list]:
    """Read one ``{"meta": ..., "rows": [...]}`` artifact; reject the
    legacy unstamped formats with an actionable error."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, (list,)) or (
            isinstance(doc, dict) and not ({"meta", "rows"} <= set(doc))):
        raise UnstampedArtifact(
            f"{path} is unstamped (pre-PR-5 format: bare rows without a "
            "provenance stamp); run `python -m benchmarks.migrate_legacy` "
            "to convert it, or regenerate via benchmarks/run.py"
        )
    meta, rows = doc["meta"], doc["rows"]
    if not isinstance(meta, dict) or "git_commit" not in meta:
        raise UnstampedArtifact(
            f"{path} carries no git_commit in its stamp; regenerate it")
    return meta, list(rows)


def load_baselines(dir: str = EXPERIMENTS,
                   benches: dict = BASELINES) -> dict:
    """bench key -> (meta, rows) for every committed artifact present."""
    out = {}
    for bench, fname in benches.items():
        path = os.path.join(dir, fname)
        if os.path.exists(path):
            out[bench] = load_stamped(path)
    return out


def load_bundle(path: str) -> dict:
    """Read a ``--save-fresh`` bundle: ``{"meta":..., "benches": {...}}``
    (same stamp discipline as the per-bench artifacts)."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "meta" not in doc or "benches" not in doc:
        raise UnstampedArtifact(
            f"{path} is not a stamped regress bundle "
            '({"meta":..., "benches":...}); re-save with --save-fresh')
    return {b: (doc["meta"], rows) for b, rows in doc["benches"].items()}


def save_bundle(path: str, fresh: dict) -> None:
    from benchmarks.common import env_stamp

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"meta": env_stamp(),
                   "benches": {b: rows for b, (_, rows) in fresh.items()}},
                  f, indent=1)


# ------------------------------------------------------------ fresh runs

def run_fresh(quick: bool, only: str = "") -> dict:
    """Re-measure at the committed configuration.  ``--quick`` keeps to
    the cheap cells: the fused-scan sizes the committed BENCH_topk holds
    and the scan-engine half of the serving sweep (same n/k/batches, so
    rows match identity-for-identity)."""
    from benchmarks import bench_serving, bench_topk_kernel

    out = {}
    if not only or "topk" in only:
        print("== fresh: topk_kernel ==", flush=True)
        # iters=9: the quick cells finish in ms, where a 3-sample median
        # swings well past the tolerance on a shared machine
        rows = bench_topk_kernel.run(
            ns=(4096, 16384) if quick else (4096, 65536, 524288), iters=9)
        out["topk_kernel"] = ({}, rows)
    if not only or "serving" in only:
        print("== fresh: serving ==", flush=True)
        rows = bench_serving.run(
            n=2048, batches=8, k=10, shards=bench_serving.machine_shards(2),
            budget=256, rerank=64,
            engines="brute,ivf_flat" if quick
            else "brute,ivf_flat,nsw,infinity",
            train_steps=150 if quick else 300)
        out["serving"] = ({}, rows)
    if not only or "load" in only:
        from benchmarks import bench_load

        print("== fresh: load ==", flush=True)
        # same identity columns as the committed artifact (n/k/capacity/
        # max_batch/load_fracs); quick keeps to brute and shorter cells —
        # duration is a measurement, not identity
        rows = bench_load.run(
            n=2048, k=10, engines="brute" if quick else "brute,ivf_flat",
            load_fracs=(0.5, 1.0, 2.0),
            duration_s=0.8 if quick else 1.5,
            train_steps=150 if quick else 200)
        out["load"] = ({}, rows)
    if not quick and (not only or "infinity" in only):
        from benchmarks import bench_infinity
        import math

        print("== fresh: infinity ==", flush=True)
        rows = bench_infinity.run(
            n=2048, qbatch=512, qs=(2.0, 4.0, 8.0, math.inf),
            budget=1024, rerank=256, train_steps=300, proj_sample=512,
            repeats=3)
        out["infinity"] = ({}, rows)
    return out


# ------------------------------------------------------------ comparison

def _scalar(v) -> bool:
    return isinstance(v, (str, int, float, bool)) or v is None


def row_identity(row: dict) -> tuple:
    """The non-measurement scalar columns — what makes two rows "the same
    cell" across runs."""
    return tuple(sorted(
        (k, str(v)) for k, v in row.items()
        if k not in MEASUREMENT_KEYS and _scalar(v)
    ))


def match_rows(base_rows: list, fresh_rows: list) -> list:
    """[(identity, base_row, fresh_row)] — duplicate identities (e.g. the
    topk size sweep keyed only by metric) pair up by ordinal."""
    def group(rows):
        g: dict = {}
        for r in rows:
            g.setdefault(row_identity(r), []).append(r)
        return g

    gb, gf = group(base_rows), group(fresh_rows)
    out = []
    for ident, brs in gb.items():
        frs = gf.get(ident, [])
        for b, f in zip(brs, frs):
            out.append((ident, b, f))
    return out


def speed_scale(matched_all: list) -> tuple[float, int]:
    """Median fresh/baseline latency ratio across every matched row of
    every bench — the machine-speed normalizer.  Clamped to [1/8, 8]; 1.0
    when there are too few samples to estimate."""
    ratios = []
    for _, b, f in matched_all:
        for key in LATENCY_KEYS:
            if key in b and key in f and b[key] and f[key]:
                ratios.append(float(f[key]) / float(b[key]))
    if len(ratios) < MIN_SCALE_SAMPLES:
        return 1.0, len(ratios)
    return float(np.clip(np.median(ratios), 1 / 8, 8)), len(ratios)


def compare(bench: str, matched: list, *, scale: float, rel_tol: float,
            recall_tol: float, comp_tol: float,
            shed_tol: float = 0.2) -> list:
    """Threshold policy (module docstring) over one bench's matched rows;
    returns finding dicts, ``regression=True`` where a hard limit was
    crossed, ``warn=True`` where only the unclamped suite trend was."""
    findings = []
    gate = max(scale, 1.0)  # never demand fresh be *faster* than baseline

    def add(ident, metric, base, fresh, limit, bad, better, warn=False):
        findings.append({
            "bench": bench, "identity": dict(ident), "metric": metric,
            "baseline": base, "fresh": fresh, "limit": limit,
            "better": better, "regression": bool(bad),
            "warn": bool(warn and not bad),
        })

    for ident, b, f in matched:
        for key in LATENCY_KEYS:
            if key in b and key in f and b[key]:
                limit = float(b[key]) * gate * (1.0 + rel_tol)
                trend = float(b[key]) * scale * (1.0 + rel_tol)
                add(ident, key, float(b[key]), float(f[key]), limit,
                    float(f[key]) > limit, "lower",
                    warn=float(f[key]) > trend)
        for key in ("qps", "goodput_qps", "knee_qps"):
            if key in b and key in f and b[key]:
                limit = float(b[key]) / gate / (1.0 + rel_tol)
                trend = float(b[key]) / scale / (1.0 + rel_tol)
                add(ident, key, float(b[key]), float(f[key]), limit,
                    float(f[key]) < limit, "higher",
                    warn=float(f[key]) < trend)
        if "shed_rate" in b and "shed_rate" in f and b["shed_rate"] is not None:
            # a fraction of offered load, offered as multiples of measured
            # saturation: machine-independent, absolute band
            limit = float(b["shed_rate"]) + shed_tol
            add(ident, "shed_rate", float(b["shed_rate"]),
                float(f["shed_rate"]), limit,
                float(f["shed_rate"]) > limit, "lower")
        for key in b:
            if key.startswith("recall") and key in f \
                    and _scalar(b[key]) and b[key] is not None:
                limit = float(b[key]) - recall_tol
                add(ident, key, float(b[key]), float(f[key]), limit,
                    float(f[key]) < limit, "higher")
        if "mean_comparisons" in b and "mean_comparisons" in f and b["mean_comparisons"]:
            limit = float(b["mean_comparisons"]) * (1.0 + comp_tol)
            add(ident, "mean_comparisons", float(b["mean_comparisons"]),
                float(f["mean_comparisons"]), limit,
                float(f["mean_comparisons"]) > limit, "lower")
    return findings


def inject(fresh: dict, spec: str, match: str) -> int:
    """Multiply ``metric`` by ``factor`` on fresh rows whose columns carry
    every ``key=val`` of ``match`` — the synthetic-regression self-test
    CI runs to prove the sentinel trips."""
    metric, factor = spec.split("=", 1)
    factor = float(factor)
    wanted = dict(kv.split("=", 1) for kv in match.split(",")) if match else {}
    hit = 0
    for _, (_, rows) in fresh.items():
        for r in rows:
            if metric not in r:
                continue
            if all(str(r.get(k)) == v for k, v in wanted.items()):
                r[metric] = float(r[metric]) * factor
                hit += 1
    return hit


# --------------------------------------------------------------- report

def render_report(findings: list, *, scale: float, scale_n: int,
                  rel_tol: float, recall_tol: float, comp_tol: float,
                  unmatched: dict, injected: int) -> str:
    regs = [f for f in findings if f["regression"]]
    warns = [f for f in findings if f.get("warn")]
    lines = [
        "# Bench regression report",
        "",
        f"- compared: **{len(findings)}** metric cells across "
        f"{len({f['bench'] for f in findings})} benches",
        f"- regressions: **{len(regs)}** (warnings: {len(warns)} — slower "
        "than the suite-median speedup but not than baseline)",
        f"- speed scale (median fresh/baseline latency ratio over "
        f"{scale_n} samples): **{scale:.3f}**",
        f"- thresholds: latency/QPS ±{rel_tol:.0%} (speed-normalized), "
        f"recall floor −{recall_tol}, comparisons +{comp_tol:.0%}",
    ]
    if injected:
        lines.append(f"- synthetic injection active on {injected} row(s) "
                     "(self-test mode)")
    for bench, n in unmatched.items():
        if n:
            lines.append(f"- note: {n} baseline row(s) in `{bench}` had no "
                         "fresh counterpart (not re-run at this config)")
    lines += ["", "| bench | cell | metric | baseline | fresh | limit | verdict |",
              "|---|---|---|---|---|---|---|"]

    def fmt(x):
        return f"{x:.4g}" if isinstance(x, float) else str(x)

    for f in sorted(findings, key=lambda f: (not f["regression"],
                                             not f.get("warn"), f["bench"])):
        ident = ",".join(f"{k}={v}" for k, v in sorted(f["identity"].items())
                         if k in ("engine", "mode", "dtype", "q", "shards",
                                  "n", "metric", "cell", "load_frac"))
        lines.append(
            f"| {f['bench']} | {ident} | {f['metric']} | "
            f"{fmt(f['baseline'])} | {fmt(f['fresh'])} | {fmt(f['limit'])} | "
            f"{'**REGRESSION**' if f['regression'] else 'warn (suite trend)' if f.get('warn') else 'ok'} |"
        )
    return "\n".join(lines) + "\n"


# ------------------------------------------------------------------ CLI

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="cheap cells only (the CI gate)")
    ap.add_argument("--only", default="", help="substring filter on benches")
    ap.add_argument("--check", action="store_true",
                    help="validate committed artifacts are stamped; no run")
    ap.add_argument("--dir", default=EXPERIMENTS)
    ap.add_argument("--rel-tol", type=float, default=0.15)
    ap.add_argument("--recall-tol", type=float, default=0.05)
    ap.add_argument("--comp-tol", type=float, default=0.25)
    ap.add_argument("--shed-tol", type=float, default=0.2,
                    help="absolute shed-rate band for BENCH_load rows")
    ap.add_argument("--baseline", default=None, metavar="BUNDLE",
                    help="compare against this saved bundle instead of the "
                         "committed artifacts")
    ap.add_argument("--fresh", default=None, metavar="BUNDLE",
                    help="reuse saved fresh rows instead of re-measuring")
    ap.add_argument("--save-fresh", default=None, metavar="PATH")
    ap.add_argument("--inject", default=None, metavar="METRIC=FACTOR",
                    help="self-test: scale a fresh metric before comparing")
    ap.add_argument("--inject-match", default="", metavar="K=V[,K=V]")
    ap.add_argument("--report", default="REGRESSIONS.md")
    args = ap.parse_args(argv)

    # stamp validation runs on every path (the --check fast path stops here)
    try:
        baselines = (load_bundle(args.baseline) if args.baseline
                     else load_baselines(args.dir))
        if not args.baseline:
            import glob as glob_lib

            for path in sorted(
                    glob_lib.glob(os.path.join(args.dir, "BENCH_*.json"))):
                load_stamped(path)
    except UnstampedArtifact as e:
        print(f"REJECTED: {e}", file=sys.stderr)
        return 2
    if args.check:
        print(f"all artifacts under {args.dir} are stamped")
        return 0
    if not baselines:
        print("no stamped baselines found: nothing to gate", file=sys.stderr)
        return 2

    if args.fresh:
        try:
            fresh = load_bundle(args.fresh)
        except UnstampedArtifact as e:
            print(f"REJECTED: {e}", file=sys.stderr)
            return 2
    else:
        fresh = run_fresh(args.quick, args.only)
    if args.save_fresh:
        save_bundle(args.save_fresh, fresh)
        print(f"fresh rows -> {args.save_fresh}")

    injected = 0
    if args.inject:
        injected = inject(fresh, args.inject, args.inject_match)
        if injected == 0:
            print("WARNING: --inject matched no fresh rows", file=sys.stderr)

    matched_by_bench, unmatched = {}, {}
    for bench in sorted(set(baselines) & set(fresh)):
        if args.only and args.only not in bench:
            continue
        m = match_rows(baselines[bench][1], fresh[bench][1])
        matched_by_bench[bench] = m
        unmatched[bench] = len(baselines[bench][1]) - len(m)
    all_matched = [t for m in matched_by_bench.values() for t in m]
    if not all_matched:
        print("no fresh row matched any baseline row: the committed "
              "artifacts were produced at a different config", file=sys.stderr)
        return 2

    scale, scale_n = speed_scale(all_matched)
    findings = []
    for bench, m in matched_by_bench.items():
        findings += compare(bench, m, scale=scale, rel_tol=args.rel_tol,
                            recall_tol=args.recall_tol,
                            comp_tol=args.comp_tol, shed_tol=args.shed_tol)
    report = render_report(
        findings, scale=scale, scale_n=scale_n, rel_tol=args.rel_tol,
        recall_tol=args.recall_tol, comp_tol=args.comp_tol,
        unmatched=unmatched, injected=injected)
    with open(args.report, "w") as f:
        f.write(report)
    regs = [f for f in findings if f["regression"]]
    print(f"{len(findings)} cells compared, scale={scale:.3f}, "
          f"{len(regs)} regression(s) -> {args.report}")
    for f in regs:
        print(f"  REGRESSION {f['bench']} {f['metric']}: "
              f"{f['fresh']:.4g} vs limit {f['limit']:.4g} "
              f"(baseline {f['baseline']:.4g})")
    return 1 if regs else 0


if __name__ == "__main__":
    sys.exit(main())
