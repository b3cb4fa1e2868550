"""End-to-end serving demo: one corpus, every engine, hot-swapped live.

Builds a ``SearchServer`` over a synthetic corpus, then swaps the serving
engine through the ``core/index`` registry (brute -> ivf_flat -> nsw ->
infinity by default) WITHOUT reloading the corpus — the production shape of
Fig. 18's online path behind one uniform ``build/search`` contract.  Each
engine reports p50/p99 latency, QPS, comparisons/query and recall against
the registry's own brute-force oracle.

  PYTHONPATH=src python examples/serve_search.py [--n 10000] [--shards 2] \
      [--engines ivf_flat,nsw,infinity] [--live]

``--live`` serves every engine through the ``core/live`` mutable wrapper
and runs a churn burst (upserts + deletes) before the measurement;
``server.stats()`` then shows the segment composition — frozen size, delta
fill, tombstones, generation — alongside p50/p99/QPS, the numbers an
operator watches to see compaction pressure.

``--filter-demo`` attaches demo attribute columns (``category`` c0..c7,
``score`` uniform [0,1)) and, after the engine sweep, answers one query
twice against the running server — unfiltered, then with a categorical +
range predicate — printing the top-k side by side so the constrained
answer is visibly drawn from the passing rows only.

``--quant`` serves every engine with the reserved ``quant`` registry cfg
key (``core/quant``, DESIGN.md §13): the corpus is mirrored as
per-dimension int8 codes, the scan engines' first pass reads 1 byte/dim
and a pow2 shortlist is exactly reranked in f32; ``server.stats()`` then
reports ``quant_bytes`` — the code-store footprint — next to memory/QPS.

``--beam-demo`` runs the infinity engine's two traversal modes head to
head on the same query batch (DESIGN.md §15): the per-query best-first
host loop vs the one-dispatch batched beam, printing p50 latency, QPS,
comparisons and recall side by side.  Batched serving auto-routes to the
beam (``mode="auto"``); this flag makes the win visible.

``--metrics-port`` enables the ``core/telemetry`` registry and serves its
Prometheus text exposition at ``http://127.0.0.1:PORT/metrics`` from a
stdlib ``http.server`` thread for the whole run (DESIGN.md §16);
``--hold-metrics SECONDS`` keeps the process (and the endpoint) alive
after the sweep so a scraper can collect the final counters, and
``--trace-out PATH`` writes the bounded trace ring as Chrome/Perfetto
``trace_event`` JSON on exit — load it at ui.perfetto.dev for the
per-stage flamegraph.

``--deadline-ms`` / ``--chaos`` exercise fault-tolerant serving
(DESIGN.md §14): ``--chaos JSON`` arms a deterministic
``core/chaos.FaultPlan`` (e.g. ``'{"seed": 0, "rules": [{"site":
"search", "kind": "latency", "rate": 0.1, "ms": 20}]}'``) on every
served engine, and ``--deadline-ms`` runs each request through the
degradation controller — the comparison budget shrinks with the
remaining deadline, transient faults retry with capped backoff, dead
shards are masked out of the merge.  The per-engine line then reports
degraded/retry counts and the server's health next to recall.

Reading the observatory (DESIGN.md §17)
---------------------------------------

``--probe-rate R`` arms the online recall probe: a seeded deterministic
R-fraction of served queries is shadowed through the exact brute-force
oracle and a sliding-window recall@k estimate with its Wilson 95%
interval accumulates as the sweep runs.  ``--probe-slo FLOOR`` adds the
quality SLO: if the interval's *upper* bound sits below FLOOR over
enough probes, the server walks its health machine to DEGRADED and
counts ``quality_degraded_total``.  The per-engine stats line grows a
``quality`` segment (estimate [lo, hi] over probed count), and the
Prometheus exposition carries ``recall_estimate{engine=...,q=...,k=...}``
/ ``probe_total`` — recall as a *live time series*, not a post-hoc bench
column.

``--roofline`` profiles each engine's compiled serving program after its
measurement: the batched ``search`` dispatch is lowered and compiled
AOT, its optimized HLO pushed through the loop-aware ``dist/roofline``
accounting, and the per-program flops / HBM bytes / arithmetic intensity
/ predicted-vs-measured time printed and exported as
``roofline_*{program=search:<engine>}`` gauges — ``roofline_pct_of_peak``
says how close that program runs to the chip's ceiling; it exists only
on a TPU (a CPU time measures no chip).

Together with ``--metrics-port`` this is the full observatory: scrape
``/metrics`` and you get latency (``search_seconds``), quality
(``recall_estimate`` + CI bounds), and efficiency (``roofline_*``) for
the serving process in one pull.

``--load-demo`` mounts the async overload runtime (DESIGN.md §18) on the
last served engine and pushes a deliberately over-capacity burst through
it: a small bounded queue admits what fits, rejects the rest with
``retry_after``, forms continuous batches, and reports every outcome
explicitly.  The point of the demo is the metric surface — after it runs
the exposition carries ``queue_depth``, ``admission_total{outcome=...}``,
``shed_total{reason=...}``, ``batch_fill`` and ``breaker_state``, so a
scraper sees the overload series next to the latency/quality/efficiency
ones (CI greps exactly these).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from benchmarks.common import recall_at_k
from repro.core import index as index_lib
from repro.core import telemetry as telem
from repro.data import synthetic
from repro.launch.serve import SearchServer, default_cfg


def start_metrics_server(port: int):
    """Serve ``telem.metrics_text()`` at /metrics on a daemon thread.

    Stdlib-only (DESIGN.md §16): a tiny ``http.server`` handler that
    renders the process-wide registry fresh on every GET — the pull model
    Prometheus expects.  Returns the bound (host, port) so callers can
    print the scrape target (port 0 binds an ephemeral port)."""
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 (stdlib handler contract)
            if self.path.rstrip("/") in ("", "/metrics".rstrip("/")):
                body = telem.metrics_text().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_error(404)

        def log_message(self, *a):  # keep the demo's stdout clean
            pass

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd.server_address


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10000)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--batches", type=int, default=20)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--budget", type=int, default=256)
    ap.add_argument("--rerank", type=int, default=64)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--engines", default="brute,ivf_flat,nsw,infinity",
                    help="comma list of registry keys to hot-swap through")
    ap.add_argument("--train-steps", type=int, default=900)
    ap.add_argument("--live", action="store_true",
                    help="serve through the mutable live wrapper with a churn burst")
    ap.add_argument("--delta-cap", type=int, default=512)
    ap.add_argument("--filter-demo", action="store_true",
                    help="attach demo attribute columns and print a filtered "
                         "vs. unfiltered top-k comparison after the sweep")
    ap.add_argument("--quant", action="store_true",
                    help="serve on int8 corpus codes (the 'quant' registry "
                         "cfg key): 1 byte/dim first pass + exact f32 rerank")
    ap.add_argument("--beam-demo", action="store_true",
                    help="after the sweep, race the infinity engine's "
                         "best_first and beam traversals on one batch "
                         "(DESIGN.md §15)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline: budget shrinks as it drains, "
                         "transient faults retry, dead shards are masked "
                         "out (DESIGN.md §14)")
    ap.add_argument("--chaos", default=None, metavar="JSON",
                    help="deterministic core/chaos FaultPlan spec armed on "
                         "every served engine; sites: search/shard/build/"
                         "compact/delta/snapshot")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="enable core/telemetry and serve Prometheus "
                         "exposition at http://127.0.0.1:PORT/metrics "
                         "(0 = ephemeral port) for the whole run")
    ap.add_argument("--hold-metrics", type=float, default=0.0,
                    metavar="SECONDS",
                    help="keep the process (and /metrics) alive this long "
                         "after the sweep so a scraper can collect the "
                         "final counters")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the telemetry trace ring as Chrome/Perfetto "
                         "trace_event JSON on exit (enables telemetry)")
    ap.add_argument("--probe-rate", type=float, default=None, metavar="R",
                    help="shadow this fraction of served queries through "
                         "the exact oracle: sliding-window recall@k with "
                         "Wilson CI in stats()['quality'] and the "
                         "recall_estimate gauge (DESIGN.md §17)")
    ap.add_argument("--probe-slo", type=float, default=None, metavar="FLOOR",
                    help="sustained probe recall below FLOOR walks server "
                         "health to DEGRADED (requires --probe-rate)")
    ap.add_argument("--load-demo", action="store_true",
                    help="after the sweep, serve an over-capacity burst "
                         "through the async overload runtime so the "
                         "queue_depth / admission_total / breaker_state "
                         "series exist in /metrics (DESIGN.md §18)")
    ap.add_argument("--roofline", action="store_true",
                    help="after each engine's sweep, profile its compiled "
                         "serving program (flops/HBM/intensity/%%-of-peak) "
                         "and export roofline_* gauges")
    args = ap.parse_args()

    if args.metrics_port is not None or args.trace_out:
        telem.enable()
    if args.metrics_port is not None:
        host, port = start_metrics_server(args.metrics_port)
        print(f"metrics: http://{host}:{port}/metrics", flush=True)

    n_q = args.batch * args.batches
    X = synthetic.make("manifold", args.n + n_q, seed=0)
    corpus, queries = X[: args.n], X[args.n :]
    attrs = None
    if args.filter_demo:
        from repro.launch.serve import demo_attrs

        attrs = demo_attrs(args.n)
    batches = [queries[b * args.batch : (b + 1) * args.batch]
               for b in range(args.batches)]

    # oracle once, reused for every engine's recall
    gt = index_lib.build("brute", corpus, {}).search(queries, k=args.k)
    gt_idx = np.asarray(gt.idx)

    engines = [e.strip() for e in args.engines.split(",") if e.strip()]
    server = None
    print(f"corpus n={args.n}, {n_q} queries, k={args.k}, shards={args.shards}")
    for engine in engines:
        cfg = default_cfg(engine, budget=args.budget, rerank=args.rerank,
                          train_steps=args.train_steps)
        if server is None:
            import json as json_lib

            probe = None
            if args.probe_rate is not None:
                probe = {"rate": args.probe_rate, "k": args.k}
                if args.probe_slo is not None:
                    probe["slo_floor"] = args.probe_slo
            server = SearchServer(corpus, engine=engine, shards=args.shards,
                                  cfg=cfg, live=args.live,
                                  delta_cap=args.delta_cap, attrs=attrs,
                                  quant=args.quant,
                                  probe=probe,
                                  chaos=json_lib.loads(args.chaos)
                                  if args.chaos else None)
        else:
            server.swap(engine, shards=args.shards, cfg=cfg)  # hot-swap
        if args.live:
            # churn burst BEFORE measuring: the delta + tombstones are live
            # during the latency sweep, which is the realistic serving state
            rng = np.random.default_rng(7)
            new_ids = server.upsert(
                rng.normal(size=(args.batch, corpus.shape[1])).astype(np.float32))
            server.delete(new_ids[: len(new_ids) // 2])
        stats = server.serve(batches, k=args.k, budget=args.budget,
                             deadline_ms=args.deadline_ms)
        res = server.query(queries, k=args.k, budget=args.budget,
                           deadline_ms=args.deadline_ms)
        if args.live:
            # the churn changed the served corpus: score against an oracle
            # over the index's own logical view, with slot ids mapped to it
            logical = server.index.corpus()
            gt_live = index_lib.build("brute", logical, {}).search(
                queries, k=args.k)
            s2l = server.index.slot_to_logical()
            idx = np.asarray(res.idx)
            mapped = np.where(idx >= 0, s2l[np.maximum(idx, 0)], -1)
            recall = recall_at_k(mapped, np.asarray(gt_live.idx), args.k)
        else:
            recall = recall_at_k(np.asarray(res.idx), gt_idx, args.k)
        print(
            f"  {engine:10s} build={stats['build_s']:6.1f}s "
            f"p50={stats['p50_ms']:6.1f}ms p99={stats['p99_ms']:6.1f}ms "
            f"qps={stats['qps']:7.0f} comps={stats['mean_comparisons']:7.0f} "
            f"recall@{args.k}={recall:.3f}"
        )
        # the operator view: cumulative latency percentiles + (when --live)
        # the segment composition that signals when a compaction is due
        s = server.stats()
        line = (f"    stats: queries={s['queries']} p50={s.get('p50_ms', 0):.1f}ms "
                f"p99={s.get('p99_ms', 0):.1f}ms qps={s.get('qps', 0):.0f}")
        if s["live"]:
            line += (f" | gen={s['generation']} frozen={s['frozen_size']} "
                     f"delta={s['delta_fill']}/{s['delta_cap']} "
                     f"tombstones={s['tombstones']} alive={s['n_alive']}")
        if s.get("quant_bytes"):
            line += (f" | quant={s['quant_bytes']}B codes "
                     f"of {s['memory_bytes']}B total")
        if args.deadline_ms is not None or args.chaos:
            line += (f" | health={s['health']} "
                     f"degraded={stats.get('degraded_batches', 0)} "
                     f"misses={stats.get('deadline_misses', 0)} "
                     f"retries={stats.get('retries', 0)}")
        if "quality" in s:
            qq = s["quality"]
            line += (f" | quality={qq['recall_estimate']:.3f} "
                     f"[{qq['ci_low']:.3f},{qq['ci_high']:.3f}] "
                     f"probed={qq['probed']}/{qq['seen']}")
            if qq.get("breached"):
                line += " BREACHED"
        print(line)
        if args.roofline:
            # profile THIS engine's compiled serving program while it is
            # still the one mounted (swap would recapture a different one)
            try:
                profs = server.capture_roofline(k=args.k, budget=args.budget)
                for name, blk in profs.items():
                    pct = blk.get("pct_of_peak")
                    pct = "not measured" if pct is None else f"{pct:.4%}"
                    print(f"    roofline: {name} flops={blk['flops']:.3g} "
                          f"hbm={blk['hbm_bytes']:.3g}B "
                          f"AI={blk['intensity']:.3f} "
                          f"predicted={blk['t_predicted_s'] * 1e6:.0f}us "
                          f"measured={blk.get('t_measured_s', 0) * 1e6:.0f}us "
                          f"pct_of_peak={pct} "
                          f"({blk['dominant']}-bound)")
            except Exception as e:
                print(f"    roofline: capture failed ({type(e).__name__}: {e})")

    if args.beam_demo:
        # same engine, same queries, both traversals: the host best-first
        # loop pays one device round trip per node pop; the beam pays one
        # dispatch per batch (DESIGN.md §15)
        import time

        cfg = default_cfg("infinity", budget=args.budget, rerank=args.rerank,
                          train_steps=args.train_steps)
        eng = index_lib.build("infinity", corpus, cfg)
        print(f"\n  beam demo: infinity engine, {n_q} queries, "
              f"budget={args.budget}")
        for mode in ("best_first", "beam"):
            eng.search(queries[: min(8, n_q)], k=args.k, mode=mode)  # warm
            t0 = time.perf_counter()
            res = eng.search(queries, k=args.k, mode=mode)
            np.asarray(res.idx)
            dt = time.perf_counter() - t0
            print(f"    {mode:10s} p50={dt * 1e3:8.1f}ms "
                  f"qps={n_q / dt:8.0f} "
                  f"comps={float(np.asarray(res.comparisons).mean()):7.0f} "
                  f"recall@{args.k}="
                  f"{recall_at_k(np.asarray(res.idx), gt_idx, args.k):.3f}")

    if args.filter_demo:
        # filtered vs. unfiltered, side by side, against the RUNNING server
        # (whatever engine the sweep ended on — live wrapper included): a
        # categorical isin clause AND a numeric range clause
        flt = {"category": {"isin": ["c0", "c1"]}, "score": {"range": [0.25, None]}}
        q1 = queries[:1]
        plain = server.query(q1, k=args.k, budget=args.budget)
        filt = server.query(q1, k=args.k, budget=args.budget, filter=flt)
        cats, scores = attrs["category"], np.asarray(attrs["score"])

        def describe(i):
            if i < 0:
                return "--"
            if i < args.n:
                return f"{i:5d} {cats[i]}/{scores[i]:.2f}"
            return f"{i:5d} (delta row)"

        print(f"\n  filtered-query demo on {server.engine!r}: {flt}")
        print(f"  {'unfiltered top-k':28s}   filtered top-k")
        for a, da, b, db in zip(plain.idx[0], plain.dist[0],
                                filt.idx[0], filt.dist[0]):
            print(f"    {describe(int(a)):20s} d={da:6.3f}   "
                  f"{describe(int(b)):20s} d={db:6.3f}")
        passing = [int(i) for i in filt.idx[0]
                   if 0 <= int(i) < args.n]
        assert all(cats[i] in ("c0", "c1") and scores[i] >= 0.25
                   for i in passing), "filtered answer leaked a non-passing row"
        print("  every filtered result satisfies the predicate")

    if args.load_demo:
        # over-capacity burst through the async runtime on whatever engine
        # the sweep ended on: capacity 64 vs 128 submits guarantees visible
        # rejected_capacity outcomes (and therefore the admission_total
        # series CI greps for) without needing a sustained load generator
        from repro.launch.runtime import (OverloadPolicy, Rejected,
                                          ServingRuntime)

        pol = OverloadPolicy(capacity=64, max_batch=8, flush_ms=2.0,
                             budget=args.budget)
        runtime = ServingRuntime(server, pol).start()
        outcomes: dict = {}
        rejected = 0
        try:
            tickets = []
            for j in range(128):
                try:
                    tickets.append(runtime.submit(
                        queries[j % n_q], k=args.k,
                        deadline_ms=args.deadline_ms or 250.0))
                except Rejected:
                    rejected += 1
            for t in tickets:
                r = t.result(timeout=60.0)
                outcomes[r.outcome] = outcomes.get(r.outcome, 0) + 1
        finally:
            runtime.stop()
        rs = runtime.stats()
        print(f"\n  load demo on {server.engine!r}: 128 submits through "
              f"capacity={pol.capacity} queue")
        print(f"    admitted={rs['admitted']} "
              f"rejected_capacity={rejected} outcomes={outcomes} "
              f"batches={rs['batches']} breaker={rs['breaker_state']}")

    if args.trace_out:
        print(f"trace -> {telem.dump_trace(args.trace_out)}", flush=True)
    if args.metrics_port is not None and args.hold_metrics > 0:
        import time as time_lib

        print(f"holding /metrics open for {args.hold_metrics:.0f}s", flush=True)
        time_lib.sleep(args.hold_metrics)


if __name__ == "__main__":
    main()
