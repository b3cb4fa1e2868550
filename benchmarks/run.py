"""Benchmark driver: one harness per paper table/figure (DESIGN.md §8).

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced sizes

Prints per-benchmark result lines followed by a ``name,us_per_call,derived``
CSV summary.  Roofline terms come from launch/dryrun.py (separate process —
it forces 512 host devices).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    quick = args.quick

    from repro.launch.compile_cache import place_compile_cache

    place_compile_cache()
    from benchmarks import (
        bench_ann_compare,
        bench_depth_bound,
        bench_fault,
        bench_filtered,
        bench_infinity,
        bench_learned_search,
        bench_load,
        bench_projection_search,
        bench_qpath_kernel,
        bench_quant,
        bench_scaling,
        bench_serving,
        bench_streaming,
        bench_topk_kernel,
        bench_two_stage,
    )

    suite = [
        ("depth_bound", lambda: bench_depth_bound.run(
            ns=(100, 300, 1000) if quick else (100, 300, 1000, 3000))),
        ("projection_search", lambda: bench_projection_search.run(
            n=400 if quick else 1000, n_queries=50 if quick else 100,
            qs=(1.0, 4.0, 16.0, float("inf")) if quick
            else (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, float("inf")))),
        ("learned_search", lambda: bench_learned_search.run(
            n=1500 if quick else 4000, train_steps=300 if quick else 800)),
        ("two_stage", lambda: bench_two_stage.run(
            n=1200 if quick else 3000)),
        ("scaling", lambda: bench_scaling.run(
            ns=(500, 1500) if quick else (1000, 3000, 8000))),
        ("ann_compare", lambda: bench_ann_compare.run(
            n=1200 if quick else 3000, train_steps=300 if quick else 800)),
        ("ann_compare_jaccard", lambda: bench_ann_compare.run_jaccard(
            n=800 if quick else 1200, verbose=True)),
        ("qpath_kernel", lambda: bench_qpath_kernel.run(
            ns=(128, 256) if quick else (256, 512, 1024))),
        ("topk_kernel", lambda: bench_topk_kernel.run(
            ns=(4096, 16384) if quick else (4096, 65536, 524288))),
        # engine x shard-count serving sweep, in-process: 2 shards where the
        # machine has 2 devices, else the 1-shard half and a SKIP line
        ("serving", lambda: bench_serving.run(
            n=1024 if quick else 2048, batches=4 if quick else 8,
            shards=bench_serving.machine_shards(2),
            engines="brute,ivf_flat,nsw" if quick else "brute,ivf_flat,nsw,infinity",
            train_steps=150 if quick else 300)),
        # interleaved upsert/delete/query churn through the live subsystem
        ("streaming", lambda: bench_streaming.run(
            n=512 if quick else 2048, steps=3 if quick else 6,
            ins=48 if quick else 96, dels=24 if quick else 48,
            delta_cap=96 if quick else 256,
            engines="brute,ivf_flat,nsw" if quick else "brute,ivf_flat,nsw,infinity",
            train_steps=150 if quick else 300)),
        # predicate-mask selectivity sweep through every engine
        ("filtered", lambda: bench_filtered.run(
            n=512 if quick else 2048,
            engines="brute,ivf_flat,nsw" if quick else "brute,ivf_flat,nsw,infinity",
            train_steps=150 if quick else 300)),
        # f32 vs int8 corpus codes: recall / QPS / bytes-scanned per engine
        ("quant", lambda: bench_quant.run(
            n=512 if quick else 2048,
            engines="brute,ivf_flat" if quick else "brute,ivf_flat,infinity",
            train_steps=150 if quick else 300)),
        # q-sweep x {best_first, beam} x {f32, int8}: the one-dispatch beam
        # traversal vs the host best-first loop at matched budget
        ("infinity", lambda: bench_infinity.run(
            n=512 if quick else 2048, qbatch=128 if quick else 512,
            qs=(2.0, float("inf")) if quick else (2.0, 4.0, 8.0, float("inf")),
            budget=384 if quick else 1024, rerank=128 if quick else 256,
            train_steps=150 if quick else 300,
            proj_sample=256 if quick else 512, repeats=1 if quick else 3,
            quant_modes=(False,) if quick else (False, True))),
        # open-loop offered-QPS sweep through the async runtime: goodput /
        # shed rate / bounded latency around the measured saturation knee
        ("load", lambda: bench_load.run(
            n=512 if quick else 2048,
            engines="brute" if quick else "brute,ivf_flat",
            duration_s=0.6 if quick else 1.5,
            train_steps=150 if quick else 200)),
        # injected fault-rate sweep: recall/p99 degradation under chaos
        ("fault", lambda: bench_fault.run(
            n=512 if quick else 2048, batches=4 if quick else 8,
            engines="brute,ivf_flat",
            rates=(0.0, 0.2) if quick else (0.0, 0.1, 0.3),
            train_steps=150 if quick else 300)),
    ]
    if args.only:
        suite = [(n, f) for n, f in suite if args.only in n]

    csv = ["name,us_per_call,derived"]
    results = {}
    for name, fn in suite:
        print(f"== {name} ==", flush=True)
        t0 = time.perf_counter()
        rows = fn()
        dt = time.perf_counter() - t0
        results[name] = rows
        derived = ""
        if rows and isinstance(rows, list) and isinstance(rows[0], dict):
            keys = [k for k in ("recall@1", "mean_comparisons", "worst_comparisons")
                    if k in rows[-1]]
            derived = ";".join(f"{k}={rows[-1][k]}" for k in keys)
        csv.append(f"{name},{dt * 1e6:.0f},{derived}")
        print()

    # NOTE: no aggregate bench_results.json dump — every trajectory lives
    # in its stamped per-bench artifact (benchmarks/regress.py rejects
    # unstamped rows; benchmarks/migrate_legacy.py converted the orphan).
    os.makedirs("experiments", exist_ok=True)
    if "topk_kernel" in results:
        # machine-readable perf trajectory for the hot scan path: per-size
        # latency + HBM-byte estimates, regressed against by future PRs
        bench_topk_kernel.write_artifact(results["topk_kernel"])
    if "serving" in results:
        # serving-side trajectory: QPS / p50 / p99 / comparisons per
        # engine x shard count through the registry-driven SearchServer
        bench_serving.write_artifact(results["serving"])
    if "streaming" in results:
        # live-subsystem trajectory: recall-vs-churn + QPS per engine under
        # interleaved upsert/delete/query traces
        bench_streaming.write_artifact(results["streaming"])
    if "filtered" in results:
        # filtered-search trajectory: recall/QPS/comparisons per engine
        # across the predicate selectivity sweep
        bench_filtered.write_artifact(results["filtered"])
    if "quant" in results:
        # quantized-scan trajectory: f32 vs int8 recall/QPS/bytes-scanned —
        # the bytes-moved axis of the perf record
        bench_quant.write_artifact(results["quant"])
    if "infinity" in results:
        # infinity-engine trajectory: recall/QPS/comparisons across the
        # q-sweep in both traversal modes — the beam-speedup evidence
        bench_infinity.write_artifact(results["infinity"])
    if "fault" in results:
        # fault-tolerance trajectory: recall/p99 vs injected fault rate —
        # graceful degradation, measured
        bench_fault.write_artifact(results["fault"])
    if "load" in results:
        # overload trajectory: goodput / shed rate / p99 vs offered QPS
        # with the saturation knee per engine — overload degrades the
        # offered curve, never the admitted one
        bench_load.write_artifact(results["load"])
    print("\n".join(csv))

    # roofline readout: dry-run mesh tables (when experiments/dryrun/ has
    # captures) + the search-program profiles the suite just stamped
    from benchmarks import report_roofline

    report = report_roofline.render_all()
    if report.strip():
        print("\n" + report)


if __name__ == "__main__":
    main()
