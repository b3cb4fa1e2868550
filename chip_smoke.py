"""Smoke run of the serving path on a TPU: one chip, or four with --chips 4.

  python chip_smoke.py              # one chip: every phase at 1,000,000 x 96
  python chip_smoke.py --chips 4    # four chips: the sharded phase only

A ``deep_like`` corpus (the Deep1B vector shape, d = 96) and 256 held-out
queries are made from ``--seed``; an exact float64 numpy scan, independent
of ``repro``, is the reference.  The phases drive the system through the
entry points a user calls — ``SearchServer`` (``brute`` and the paper's
``infinity`` engine at q = inf) and the async ``ServingRuntime`` in front of
it — then run the Pallas kernels, compiled by Mosaic, against their jnp
twins.  A failed check raises, and the exit code is then non-zero.

Times printed here are smoke timings of one cold run, compilation
included: they are not benchmark numbers.  The last line of stdout is one
JSON object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import embedding as embed_lib  # noqa: E402
from repro.core import metrics as metrics_lib  # noqa: E402
from repro.core import quant as quant_lib  # noqa: E402
from repro.core import qmetric  # noqa: E402
from repro.core import scan as scan_lib  # noqa: E402
from repro.core import vptree  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.launch.runtime import OverloadPolicy, ServingRuntime  # noqa: E402
from repro.launch.serve import SearchServer, default_cfg  # noqa: E402

N = 1_000_000
K = 10
N_QUERIES = 256
#: the two pow2 batch buckets served: 64 takes infinity's beam traversal,
#: 16 its best-first loop (core/search.AUTO_BEAM_MIN_BATCH)
BATCHES = {"beam": 64, "best_first": 16}
#: infinity recall@10 floors: each is a CPU rehearsal of this phase at
#: n = 100,000 (seed 0) less 0.05, and not below 0.  The rehearsal measured
#: beam 0.0012 and best_first 0.0031 — near chance: at budget 256 the q = inf
#: engine finds almost none of the true neighbours of this Gaussian corpus,
#: so these floors bind nothing.  The checks that bind on the traversal are
#: self-retrieval and the host descent below, which hold on any corpus
RECALL_FLOOR = {"beam": 0.0, "best_first": 0.0}
#: corpus rows served as queries: each should come back as its own top-1
N_SELF = 64
#: self-retrieval floors: a CPU rehearsal at n = 100,000 (seed 0) less 0.05
SELF_FLOOR = {"beam": 0.95, "best_first": 0.95}
#: held-out queries whose q = inf descent is replayed on the host
N_DESCENT = 64
QUANT_RECALL_FLOOR = 0.99
#: ids may differ from the reference only where the distances tie
TIE_RTOL = TIE_ATOL = 1e-5
DIST_RTOL = 1e-4


def say(msg: str) -> None:
    print(msg, flush=True)


class Clock:
    """Smoke timings: host seconds since the last lap."""

    def __init__(self):
        self.t = time.perf_counter()

    def lap(self) -> float:
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        return dt


# ---------------------------------------------------------------- phase 1
def require_tpu(chips: int = 1) -> dict:
    """The device JAX found; raises unless it is a TPU with ``chips``
    devices or more."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise RuntimeError(
            f"no TPU found: JAX reports platform {d0.platform!r} "
            f"({d0.device_kind}, {len(devs)} device(s))"
        )
    if len(devs) < chips:
        raise RuntimeError(f"need {chips} TPU chips, JAX reports {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


# ---------------------------------------------------------------- phase 2
def make_data(n: int, n_queries: int, seed: int):
    """(corpus (n, 96), held-out queries (n_queries, 96)) f32."""
    X = synthetic.make("deep_like", n + n_queries, seed=seed)
    return X[:n], X[n:]


def exact_topk(X: np.ndarray, Q: np.ndarray, k: int, chunk: int = 65536):
    """Exact euclidean top-k in float64 numpy, streamed over X in chunks:
    (dist (m, k), idx (m, k)) ascending."""
    Q64 = Q.astype(np.float64)
    q2 = np.sum(Q64 * Q64, axis=1)[:, None]
    m = Q.shape[0]
    best_d = np.empty((m, 0))
    best_i = np.empty((m, 0), np.int64)
    for s in range(0, X.shape[0], chunk):
        Xc = X[s : s + chunk].astype(np.float64)
        d2 = q2 + np.sum(Xc * Xc, axis=1)[None, :] - 2.0 * (Q64 @ Xc.T)
        kk = min(k, d2.shape[1])
        part = np.argpartition(d2, kk - 1, axis=1)[:, :kk]
        cand_d = np.concatenate([best_d, np.take_along_axis(d2, part, 1)], 1)
        cand_i = np.concatenate([best_i, part + s], 1)
        order = np.lexsort((cand_i, cand_d), axis=1)[:, :k]
        best_d = np.take_along_axis(cand_d, order, 1)
        best_i = np.take_along_axis(cand_i, order, 1)
    return np.sqrt(np.maximum(best_d, 0.0)), best_i


def true_dist(X: np.ndarray, Q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float64 euclidean distance of each returned id to its query."""
    if np.any(ids < 0):
        raise AssertionError(f"{int(np.sum(ids < 0))} result slots are empty (-1)")
    diff = X[ids].astype(np.float64) - Q[:, None, :].astype(np.float64)
    return np.sqrt(np.sum(diff * diff, axis=-1))


def check_ids(name: str, X, Q, ids, ref_d, ref_i) -> None:
    """Ids equal the reference, except where the distances tie."""
    tie = np.isclose(true_dist(X, Q, ids), ref_d, rtol=TIE_RTOL, atol=TIE_ATOL)
    bad = ~((ids == ref_i) | tie)
    if bad.any():
        q = int(np.argmax(bad.any(1)))
        raise AssertionError(
            f"{name}: {int(bad.any(1).sum())} of {len(ids)} queries differ "
            f"from the reference beyond ties; query {q}: got {ids[q].tolist()} "
            f"want {ref_i[q].tolist()}"
        )


def check_dists(name: str, got, want) -> None:
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: non-finite distances")
    if not np.allclose(got, want, rtol=DIST_RTOL, atol=1e-6):
        err = np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-6))
        raise AssertionError(f"{name}: distances off by {err:.3g} relative")


def recall(ids: np.ndarray, ref_i: np.ndarray) -> float:
    return float(np.mean([len(set(a) & set(b)) / ref_i.shape[1]
                          for a, b in zip(ids.tolist(), ref_i.tolist())]))


def serve_all(server, Q: np.ndarray, batch: int):
    """Every query through ``server.query`` in batches of ``batch``."""
    outs = [server.query(Q[i : i + batch], k=K) for i in range(0, len(Q), batch)]
    return (np.concatenate([o.idx for o in outs]),
            np.concatenate([o.dist for o in outs]))


# ---------------------------------------------------------------- phase 3
def phase_brute(X, Q, ref) -> SearchServer:
    """The exact engine through the server: ids and distances equal the
    reference."""
    ref_d, ref_i = ref
    clock = Clock()
    server = SearchServer(X, engine="brute",
                          cfg=default_cfg("brute", budget=256, rerank=96))
    say(f"brute: built in {clock.lap():.2f}s (smoke timing)")
    for batch in BATCHES.values():
        ids, dist = serve_all(server, Q, batch)
        t = clock.lap()
        check_ids(f"brute batch {batch}", X, Q, ids, ref_d, ref_i)
        check_dists(f"brute batch {batch}", dist, ref_d)
        say(f"brute batch {batch}: {len(Q)} queries match the exact reference "
            f"({t:.2f}s incl. compile, smoke timing)")
    return server


# ---------------------------------------------------------------- phase 4
def phase_infinity(server, X, Q, ref, *, cfg=None, seed: int = 0,
                   floors=RECALL_FLOOR, self_floors=SELF_FLOOR) -> dict:
    """The paper's engine (q = inf) behind the same server.  In both
    traversals every returned distance is the true distance of the
    returned id, recall@10 holds its floor, and corpus rows served as
    queries come back as their own top-1; the device's q = inf descent
    equals the host recursion.  Returns {traversal: (recall, self hits)}."""
    ref_d, ref_i = ref
    clock = Clock()
    server.swap("infinity", cfg=cfg or default_cfg("infinity", budget=256,
                                                    rerank=96))
    say(f"infinity: built in {clock.lap():.2f}s (smoke timing)")
    rows = np.random.default_rng(seed).choice(len(X), N_SELF, replace=False)
    out = {}
    for mode, batch in BATCHES.items():
        ids, dist = serve_all(server, Q, batch)
        self_ids, _ = serve_all(server, X[rows], batch)
        t = clock.lap()
        check_dists(f"infinity {mode}", dist, true_dist(X, Q, ids))
        r = recall(ids, ref_i)
        hit = float(np.mean(self_ids[:, 0] == rows))
        out[mode] = (r, hit)
        say(f"infinity {mode} (batch {batch}): recall@10 = {r:.4f} "
            f"(floor {floors[mode]}), self top-1 = {hit:.4f} "
            f"(floor {self_floors[mode]}; {t:.2f}s incl. compile, smoke timing)")
        if r < floors[mode]:
            raise AssertionError(
                f"infinity {mode}: recall@10 {r:.4f} below floor {floors[mode]}")
        if hit < self_floors[mode]:
            raise AssertionError(
                f"infinity {mode}: self top-1 {hit:.4f} below floor "
                f"{self_floors[mode]}")
    check_descent(server.index, Q[:N_DESCENT])
    say(f"infinity descent: {min(N_DESCENT, len(Q))} queries equal the host "
        f"recursion ({clock.lap():.2f}s, smoke timing)")
    stages = server.index.train_history["build_s"]
    say("infinity build stages (smoke timings): " + ", ".join(
        f"{name} {s:.2f}s" for name, s in stages.items()))
    return out


def check_descent(index, Q: np.ndarray) -> None:
    """The index's own search at k = 1 without rerank takes the q = inf
    descent (Theorem 1) on the device; its ids must equal the host
    recursion ``vptree.search_reference`` over the same embedded corpus."""
    got = np.asarray(index.search(Q, k=1, rerank=0).idx)[:, 0]
    Zq = np.asarray(embed_lib.apply(index.phi_params, jnp.asarray(Q)))
    Z = np.asarray(index.Z)
    want = np.array([vptree.search_reference(index.tree, z, q=math.inf, X=Z)[0]
                     for z in Zq])
    if not np.array_equal(got, want):
        bad = np.flatnonzero(got != want)
        raise AssertionError(
            f"infinity descent: {len(bad)} of {len(Q)} queries differ from the "
            f"host recursion; query {bad[0]}: got {got[bad[0]]} want "
            f"{want[bad[0]]}")


# ---------------------------------------------------------------- phase 5
def phase_runtime(server, Q, n_requests: int = 64) -> None:
    """Requests through the async runtime come back ``ok`` with the ids the
    direct ``server.query`` gives.  All are queued before the batcher
    starts, so the runtime forms the same 16-row batches as the direct
    calls."""
    max_batch = BATCHES["best_first"]
    Qr = Q[:n_requests]
    direct_i, direct_d = serve_all(server, Qr, max_batch)
    clock = Clock()
    rt = ServingRuntime(server, OverloadPolicy(max_batch=max_batch))
    try:
        tickets = [rt.submit(q, k=K, deadline_ms=600_000.0) for q in Qr]
        rt.start()
        res = [t.result(timeout=600.0) for t in tickets]
    finally:
        rt.stop()
    t = clock.lap()
    outcomes = {r.outcome for r in res}
    if outcomes != {"ok"}:
        raise AssertionError(f"runtime: outcomes {sorted(outcomes)}, want ok")
    ids = np.concatenate([r.idx for r in res])
    dist = np.concatenate([r.dist for r in res])
    same = (ids == direct_i) | np.isclose(dist, direct_d, rtol=TIE_RTOL,
                                          atol=TIE_ATOL)
    if not same.all():
        raise AssertionError(
            f"runtime: {int((~same).any(1).sum())} of {n_requests} answers "
            "differ from the direct server.query")
    say(f"runtime: {n_requests} requests ok, ids equal the direct query "
        f"({rt.counters['batches']} batches, {t:.2f}s, smoke timing)")


# ---------------------------------------------------------------- phase 6
def phase_kernels(X, Q, ref, *, seed: int, qpath_n: int = 2048,
                  pdist_n: int = 4096) -> None:
    """The Pallas kernels against their jnp twins: topk over the whole
    corpus, pdist over corpus rows at the projection's shape."""
    ref_d, ref_i = ref
    clock = Clock()
    Xd, Qd = jnp.asarray(X), jnp.asarray(Q[:64])
    valid = jnp.asarray(np.random.default_rng(seed).random(X.shape[0]) < 0.5)
    for name, metric, v in (("f32", "euclidean", None),
                            ("f32 valid", "euclidean", valid),
                            ("manhattan", "manhattan", None),
                            ("chebyshev", "chebyshev", None)):
        kd, ki = scan_lib.topk_scan(Qd, Xd, k=K, metric=metric, impl="pallas",
                                    valid=v)
        jd, ji = scan_lib.topk_scan(Qd, Xd, k=K, metric=metric, impl="jnp",
                                    valid=v)
        kd, ki, jd, ji = map(np.asarray, (kd, ki, jd, ji))
        tie = np.isclose(kd, jd, rtol=TIE_RTOL, atol=TIE_ATOL)
        if not ((ki == ji) | tie).all() or (ki < 0).any():
            raise AssertionError(f"topk pallas {name}: ids differ from jnp")
        say(f"kernel topk {name}: ids equal the jnp scan "
            f"({clock.lap():.2f}s, smoke timing)")

    A, B = Xd[: pdist_n // 4], Xd[-pdist_n:]
    for metric in ("sqeuclidean", "manhattan"):
        got = np.asarray(metrics_lib.pairwise(A, B, metric=metric, impl="pallas"))
        want = np.asarray(metrics_lib.pairwise(A, B, metric=metric, impl="jnp"))
        if not np.allclose(got, want, rtol=1e-5, atol=5e-4):
            err = float(np.max(np.abs(got - want)))
            raise AssertionError(f"pdist {metric}: off the jnp matrix by {err:.3g}")
        say(f"kernel pdist {metric} {A.shape[0]}x{B.shape[0]}: equal to jnp "
            f"within 5e-4 ({clock.lap():.2f}s, smoke timing)")

    store = quant_lib.QuantStore.build(Xd)
    codes, scales, sqnorms = store.device_view()
    w = quant_lib.shortlist_width(K, X.shape[0])
    Qa = jnp.asarray(Q)
    _, short = scan_lib.topk_scan_quant(Qa, codes, scales, k=w, impl="pallas",
                                        sqnorms=sqnorms)
    ids, _ = jax.vmap(lambda q, c: scan_lib.topk_candidates(
        q, c, Xd, k=K, metric="euclidean"))(Qa, short)
    r = recall(np.asarray(ids), ref_i)
    say(f"kernel topk int8 + f32 rerank of {w}: recall@10 = {r:.4f} "
        f"(floor {QUANT_RECALL_FLOOR}; {clock.lap():.2f}s, smoke timing)")
    if r < QUANT_RECALL_FLOOR:
        raise AssertionError(f"topk int8: recall@10 {r:.4f} below floor")

    rng = np.random.default_rng(seed + 1)
    A = jnp.asarray(rng.random((qpath_n, qpath_n), np.float32))
    B = jnp.asarray(rng.random((qpath_n, qpath_n), np.float32))
    got = np.asarray(qmetric.semiring_matmul(A, B, mode="minmax", impl="pallas"))
    want = np.asarray(qmetric.semiring_matmul(A, B, mode="minmax", impl="jnp"))
    if not np.array_equal(got, want):
        raise AssertionError("qpath minmax: kernel differs from jnp")
    say(f"kernel qpath minmax {qpath_n}x{qpath_n}: equal to jnp "
        f"({clock.lap():.2f}s, smoke timing)")


# ------------------------------------------------------- four chips only
def check_spans(server, shards: int) -> None:
    """Every leaf of the stacked shard state spans ``shards`` devices."""
    for leaf in jax.tree_util.tree_leaves(server.index.stacked):
        if len(leaf.sharding.device_set) != shards:
            raise AssertionError(
                f"shard state leaf {leaf.shape} spans "
                f"{len(leaf.sharding.device_set)} devices, want {shards}")


def phase_sharded(X, Q, ref, *, shards: int = 4, cfg=None) -> dict:
    """``--chips 4``: the corpus sharded over ``shards`` devices.  Sharded
    brute ids equal one-device brute; the shard state spans ``shards``
    devices; sharded infinity recall is printed next to one-device."""
    ref_d, ref_i = ref
    cfg = cfg or default_cfg("infinity", budget=256, rerank=96)
    clock = Clock()
    server = SearchServer(X, engine="brute",
                          cfg=default_cfg("brute", budget=256, rerank=96))
    one_i, _ = serve_all(server, Q, BATCHES["beam"])
    server.swap("brute", shards=shards)
    check_spans(server, shards)
    ids, dist = serve_all(server, Q, BATCHES["beam"])
    check_ids(f"brute {shards} shards", X, Q, ids, ref_d, ref_i)
    if not ((ids == one_i) | np.isclose(true_dist(X, Q, ids),
                                        true_dist(X, Q, one_i),
                                        rtol=TIE_RTOL, atol=TIE_ATOL)).all():
        raise AssertionError(f"brute {shards} shards: ids differ from 1 device")
    check_dists(f"brute {shards} shards", dist, ref_d)
    say(f"brute {shards} shards: ids equal 1-device brute; state spans "
        f"{shards} devices ({clock.lap():.2f}s, smoke timing)")

    out = {}
    for s in (1, shards):
        server.swap("infinity", shards=s, cfg=cfg)
        if s > 1:
            check_spans(server, s)
        ids, dist = serve_all(server, Q, BATCHES["beam"])
        check_dists(f"infinity {s} shard(s)", dist, true_dist(X, Q, ids))
        out[s] = recall(ids, ref_i)
    say(f"infinity recall@10: {out[shards]:.4f} on {shards} shards, "
        f"{out[1]:.4f} on 1 device ({clock.lap():.2f}s, smoke timing)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = place_compile_cache()
    device = require_tpu(args.chips)
    say(f"device: {device['kind']} x{device['count']}, jax {jax.__version__}, "
        f"compile cache {cache}")
    clock = Clock()
    X, Q = make_data(N, N_QUERIES, args.seed)
    ref = exact_topk(X, Q, K)
    say(f"data: {N} x {X.shape[1]} corpus, {len(Q)} queries, float64 "
        f"reference in {clock.lap():.2f}s (smoke timing)")
    if args.chips == 4:
        phase_sharded(X, Q, ref, shards=4)
    else:
        server = phase_brute(X, Q, ref)
        phase_infinity(server, X, Q, ref, seed=args.seed)
        phase_runtime(server, Q)
        phase_kernels(X, Q, ref, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
