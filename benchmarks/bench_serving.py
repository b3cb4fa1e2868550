"""Serving benchmark: QPS / p50 / p99 / comparisons per engine x shard count.

Drives ``launch/serve.SearchServer`` (the registry-driven front end) over a
synthetic corpus for every engine at 1 and ``shards`` corpus shards, in this
process, on the devices ``jax.devices()`` reports.  A shard count above the
device count raises: a CPU rehearsal of the sharded half gives itself host
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=2``).
``benchmarks/run.py`` writes the rows to ``experiments/BENCH_serving.json``.
"""
from __future__ import annotations

import argparse
import os
import sys

if __name__ == "__main__":  # standalone: python benchmarks/bench_serving.py
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def run(n=2048, batch=64, batches=8, k=10, engines="brute,ivf_flat,nsw,infinity",
        shards=2, budget=256, rerank=64, train_steps=200, proj_sample=512,
        verbose=True):
    """Measure every (engine, shard count) cell; returns the result rows.

    Telemetry is on for the sweep (DESIGN.md §16): each row carries a
    ``stages`` breakdown (per-stage comparisons + ms for the cell) so the
    serving artifact shows where each engine's latency went."""
    import jax
    import numpy as np

    from benchmarks.common import recall_at_k, stage_breakdown
    from repro.core import index as index_lib
    from repro.core import telemetry as telem
    from repro.data import synthetic
    from repro.launch.serve import SearchServer, default_cfg

    if shards > jax.device_count():
        raise RuntimeError(
            f"shards={shards} needs {shards} devices; JAX reports "
            f"{jax.device_count()} ({jax.devices()[0].platform})"
        )
    n_q = batch * batches
    X = synthetic.make("manifold", n + n_q, seed=0)
    corpus, queries = X[:n], X[n:]
    qbatches = [queries[b * batch : (b + 1) * batch] for b in range(batches)]
    gt = index_lib.build("brute", corpus, {}).search(queries, k=k)
    gt_idx = np.asarray(gt.idx)

    was_enabled = telem.enabled()
    telem.enable()
    rows = []
    server = None
    try:
        for engine in engines.split(","):
            cfg = default_cfg(engine, budget=budget, rerank=rerank,
                              train_steps=train_steps, proj_sample=proj_sample)
            for s in sorted({1, shards}):
                if s > 1 and n % s != 0:
                    # visible truncation: the artifact must not pretend the
                    # sharded half of the sweep ran
                    print(f"  SKIP {engine} shards={s}: n={n} not divisible")
                    continue
                if server is None:
                    server = SearchServer(corpus, engine=engine, shards=s, cfg=cfg)
                else:
                    server.swap(engine, shards=s, cfg=cfg)
                telem.reset()  # stage window = this (engine, shards) cell only
                stats = server.serve(qbatches, k=k, budget=budget)
                res = server.query(queries, k=k, budget=budget)
                stats["recall@k"] = recall_at_k(np.asarray(res.idx), gt_idx, k)
                stats["n"] = n
                stats["stages"] = stage_breakdown(engine)
                # compiled-program roofline for this cell's serving dispatch
                # (DESIGN.md §17); degraded to an error note, never a crash
                try:
                    profs = server.capture_roofline(batch=batch, k=k,
                                                    budget=budget)
                    stats["roofline"] = next(iter(profs.values()), None)
                except Exception as e:  # pragma: no cover
                    stats["roofline"] = {"error": f"{type(e).__name__}: {e}"[:200]}
                rows.append(stats)
    finally:
        telem.enable(was_enabled)
    if verbose:
        for rec in rows:
            print(
                f"  {rec['engine']:10s} shards={rec['shards']} "
                f"p50={rec['p50_ms']:7.1f}ms p99={rec['p99_ms']:7.1f}ms "
                f"qps={rec['qps']:8.0f} comps={rec['mean_comparisons']:7.0f} "
                f"recall@{rec['k']}={rec['recall@k']:.3f}"
            )
    return rows


def machine_shards(want: int = 2) -> int:
    """The shard count a sweep can run here: ``want``, cut to the device
    count with a visible SKIP line for the sharded half left out."""
    import jax

    have = jax.device_count()
    if have < want:
        print(f"  SKIP serving shards={want}: JAX reports {have} device(s)")
    return min(want, have)


def write_artifact(rows, path="experiments/BENCH_serving.json") -> None:
    """Single owner of the machine-readable serving-perf artifact
    (also called by benchmarks/run.py); stamped with run provenance."""
    from benchmarks.common import write_stamped

    write_stamped(path, rows)


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--engines", default="brute,ivf_flat,nsw,infinity")
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--budget", type=int, default=256)
    ap.add_argument("--rerank", type=int, default=64)
    ap.add_argument("--train-steps", type=int, default=200)
    ap.add_argument("--proj-sample", type=int, default=512)
    return ap.parse_args(argv)


if __name__ == "__main__":
    _args = _parse()
    write_artifact(run(
        n=_args.n, batch=_args.batch, batches=_args.batches, k=_args.k,
        engines=_args.engines, shards=_args.shards, budget=_args.budget,
        rerank=_args.rerank, train_steps=_args.train_steps,
        proj_sample=_args.proj_sample,
    ))
