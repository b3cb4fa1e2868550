"""Knee sweep: one set-up, then open Poisson loops at rising rates.

  python chipbench/sweep.py --config deep1m-inf --seed 11 --start 50 \
      --seconds 8 --deadline-ms 1000

Builds the configuration's system once, warms every batch shape, then
offers ``--start`` req/s, doubling (``--factor``) until the p99 latency of
all requests passes ``--deadline-ms`` or the backlog grows (answers still
due more than ``--drain-s`` after the window closed), or ``--steps`` rates
have run; then ``--refine`` rates evenly between the last rate that held
and the first that broke.  The knee is the highest rate that held.  One
JSON line per rate on standard output, and all of them in
``chipbench/out/sweep-<config>.json``.  The deadline given to the runtime
is ``--runtime-deadline-ms`` (default: none to speak of), so nothing is
shed and the latencies are the system's own.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--factor", type=float, default=2.0)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--rates", default="",
                    help="comma-separated rates to run instead of doubling")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--deadline-ms", type=float, required=True)
    ap.add_argument("--runtime-deadline-ms", type=float, default=600_000.0)
    ap.add_argument("--drain-s", type=float, default=1.0)
    ap.add_argument("--refine", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import datagen, harness, loadgen, system as system_lib

    bench = harness.load_benchmark(ROOT)
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    harness.place_compile_cache(ROOT)
    device = harness.require_tpu(1)
    data = config["data"]
    X = datagen.make(data["generator"], args.seed, 0, int(data["n"]),
                     **data.get("params", {}))
    pool = np.asarray(datagen.make(data["generator"], args.seed, 1,
                                   int(data["query_pool"]),
                                   **data.get("params", {})))
    jax.block_until_ready(X)
    system = system_lib.System(config, X, args.seed)
    system.warm(pool, 1 << 30)
    print(json.dumps({"device": device, "setup_s": time.monotonic() - T_PROCESS,
                      "build_s": system.build_stages()}), flush=True)
    rates = ([float(r) for r in args.rates.split(",") if r]
             or [args.start * args.factor ** i for i in range(args.steps)])
    rows = []

    def step(i, rate):
        system.query_log.clear()
        system.start()
        try:
            rec = loadgen.run_open(
                submit=system.submit, on_done=system.on_done,
                result=system.result, rejected=system.rejected, queries=pool,
                rate=rate, seconds=args.seconds, seed=args.seed + i,
                deadline_ms=args.runtime_deadline_ms, k=system.k)
            counters = system.counters()
        finally:
            system.stop()
        lat = rec.latency_ms()
        ok = ~np.isnan(lat)
        drain = float(np.nanmax(rec.done) - (rec.t0 + rec.seconds))
        sizes = [b for _, _, b in system.query_log]
        row = {
            "rate": rate, "requests": rec.n, "ok": int(ok.sum()),
            "goodput_qps": loadgen.goodput_qps(rec, args.deadline_ms),
            "p50_ms": loadgen.percentile_ms(rec, 50, args.deadline_ms),
            "p99_ms": loadgen.percentile_ms(rec, 99, args.deadline_ms),
            "max_ms": float(np.max(lat[ok])) if ok.any() else None,
            "drain_s": drain, "batches": counters["batches"],
            "mean_batch": float(np.mean(sizes)) if sizes else 0.0,
            "batch_64_share": float(np.mean(np.asarray(sizes) >= 64)) if sizes else 0.0,
            "lateness_ms": loadgen.lateness_ms(rec),
            "outcomes": loadgen.outcome_counts(rec),
        }
        row["held"] = bool(row["p99_ms"] <= args.deadline_ms
                           and drain <= args.drain_s)
        rows.append(row)
        print(json.dumps(row), flush=True)
        return row["held"]

    last_good = None
    for i, rate in enumerate(rates):
        if not step(i, rate):
            if last_good is not None:
                for j in range(1, args.refine + 1):
                    r = last_good + (rate - last_good) * j / (args.refine + 1)
                    if not step(len(rows), r):
                        break
            break
        last_good = rate
    held = [r["rate"] for r in rows if r["held"]]
    print(json.dumps({"knee": max(held) if held else None}), flush=True)
    os.makedirs(os.path.join(ROOT, "chipbench", "out"), exist_ok=True)
    with open(os.path.join(ROOT, "chipbench", "out",
                           f"sweep-{args.config}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
