"""Load generators: an open Poisson loop and a closed loop of callers.

Taken from ``benchmarks/bench_load._open_loop_cell`` (a pre-scheduled
Poisson open loop) with three faults repaired:

* a request is timed from when it was DUE, not from when the generator got
  round to submitting it, so a stall in the generator or the server shows
  in every later request's latency;
* every request due in the window is counted: one that is shed, rejected,
  fails or never answers is a miss, ranked above every answered one;
* there is no chaos ``slow_search`` spike in front of each dispatch.

The arrivals of an open loop are a fixed set for a given rate and window:
the quantiles of the exponential gap distribution, put in an order drawn
from the seed.  Every seed thus offers the same work in another order, so
a change of seed does not change how much work a run has.  The generator
reports how late it ran (submit time less due time).

The system is reached through three callables, so that the generator knows
nothing of the program: ``submit(q, deadline_ms) -> handle`` (raises
``rejected`` when admission refuses), ``on_done(handle, fn)`` (``fn()`` runs
when the answer resolves) and ``result(handle, timeout) -> answer`` where
an answer has ``outcome``, ``idx``, ``dist`` and ``queue_ms``.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, Optional

import numpy as np

#: a miss (shed, rejected, failed, unanswered) is given this many deadlines
#: as its latency, or twice the slowest answer where that is more, so it
#: ranks above every answered request
MISS_DEADLINES = 10.0


@dataclasses.dataclass
class Record:
    """What the client saw of every request due in the window."""

    qrow: np.ndarray  # (N,) pool row of each request
    due: np.ndarray  # (N,) monotonic seconds the request was due
    submit: np.ndarray  # (N,) monotonic seconds it was submitted
    done: np.ndarray  # (N,) monotonic seconds it resolved, nan if never
    outcome: list  # (N,) "ok", a shed outcome, "rejected_<why>", "error", "unanswered"
    idx: np.ndarray  # (N, k) served ids, -1 where none
    dist: np.ndarray  # (N, k) served distances
    queue_ms: np.ndarray  # (N,) the runtime's queue wait, nan where none
    t0: float  # window start (monotonic)
    seconds: float  # window length

    @property
    def n(self) -> int:
        return len(self.outcome)

    def ok(self) -> np.ndarray:
        return np.array([o == "ok" for o in self.outcome], bool)

    def latency_ms(self) -> np.ndarray:
        """Client latency from due time to answer, ms; nan for a miss."""
        lat = (self.done - self.due) * 1e3
        return np.where(self.ok(), lat, np.nan)


def exponential_quantiles(n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of the unit exponential distribution."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p)


def open_schedule(rate: float, seconds: float, seed: int, pool: int):
    """Due offsets (s, ascending, in [0, seconds)) and pool rows of an
    open Poisson loop at ``rate`` req/s: ``round(rate * seconds)`` arrivals
    whose gaps are the exponential quantiles in a seeded order."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed), 1])
    gaps = rng.permutation(exponential_quantiles(n))
    due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    rows = rng.integers(0, pool, size=n)
    return due, rows


def _empty(n: int, k: int):
    return (np.full(n, np.nan), ["unanswered"] * n,
            np.full((n, k), -1, np.int64), np.full((n, k), np.inf),
            np.full(n, np.nan))


def _settle(rec_handles, outcome, idx, dist, queue_ms, result, deadline_abs):
    """Wait for every handle until ``deadline_abs`` and read its answer."""
    for j, h in rec_handles:
        if h is None:
            continue
        try:
            r = result(h, max(0.0, deadline_abs - time.monotonic()))
        except TimeoutError:
            outcome[j] = "unanswered"
            continue
        except Exception:  # a dispatch fault surfaced through the ticket
            outcome[j] = "error"
            continue
        outcome[j] = r.outcome
        if r.outcome == "ok":
            idx[j] = np.asarray(r.idx)[0]
            dist[j] = np.asarray(r.dist)[0]
        queue_ms[j] = float(r.queue_ms)


def run_open(*, submit: Callable, on_done: Callable, result: Callable,
             rejected: type, queries: np.ndarray, rate: float, seconds: float,
             seed: int, deadline_ms: float, k: int, grace_s: float = 60.0,
             on_start: Optional[Callable] = None) -> Record:
    """Drive an open Poisson loop from one thread; returns the record."""
    due_off, rows = open_schedule(rate, seconds, seed, len(queries))
    n = len(rows)
    done, outcome, idx, dist, queue_ms = _empty(n, k)
    sub = np.full(n, np.nan)
    handles = []
    if on_start is not None:
        on_start()
    t0 = time.monotonic()
    due = t0 + due_off
    for j in range(n):
        wait = due[j] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        now = time.monotonic()
        sub[j] = now
        left = deadline_ms - (now - due[j]) * 1e3
        try:
            h = submit(queries[rows[j]], max(left, 1e-3))
        except rejected as e:
            outcome[j] = f"rejected_{getattr(e, 'reason', 'other')}"
            done[j] = time.monotonic()
            handles.append((j, None))
            continue

        def stamp(j=j):
            done[j] = time.monotonic()

        on_done(h, stamp)
        handles.append((j, h))
    end_abs = t0 + seconds + deadline_ms / 1e3 + grace_s
    _settle(handles, outcome, idx, dist, queue_ms, result, end_abs)
    return Record(rows, due, sub, done, outcome, idx, dist, queue_ms, t0,
                  seconds)


def run_closed(*, submit: Callable, on_done: Callable, result: Callable,
               rejected: type, queries: np.ndarray, clients: int,
               seconds: float, seed: int, deadline_ms: float, k: int,
               grace_s: float = 60.0,
               on_start: Optional[Callable] = None) -> Record:
    """``clients`` callers, each sending its next request when its last
    answer returns, for ``seconds``; a request is due when it is sent."""
    rng = np.random.default_rng([int(seed), 2])
    per_client = [list(rng.integers(0, len(queries), size=1 << 16))
                  for _ in range(clients)]
    logs = [[] for _ in range(clients)]  # (row, t_sub, t_done, outcome, idx, dist, queue_ms)
    if on_start is not None:
        on_start()
    t0 = time.monotonic()
    t_end = t0 + seconds
    give_up = t_end + deadline_ms / 1e3 + grace_s

    def caller(c: int):
        rows = per_client[c]
        for row in rows:
            ts = time.monotonic()
            if ts >= t_end:
                return
            try:
                h = submit(queries[row], deadline_ms)
            except rejected as e:
                logs[c].append((row, ts, time.monotonic(),
                                f"rejected_{getattr(e, 'reason', 'other')}",
                                None, None, math.nan))
                continue
            try:
                r = result(h, max(0.0, give_up - time.monotonic()))
            except TimeoutError:
                logs[c].append((row, ts, math.nan, "unanswered", None, None,
                                math.nan))
                return
            except Exception:
                logs[c].append((row, ts, time.monotonic(), "error", None,
                                None, math.nan))
                continue
            logs[c].append((row, ts, time.monotonic(), r.outcome,
                            np.asarray(r.idx)[0], np.asarray(r.dist)[0],
                            float(r.queue_ms)))

    threads = [threading.Thread(target=caller, args=(c,), daemon=True,
                                name=f"caller-{c}") for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(1.0, give_up - time.monotonic() + 5.0))
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a closed-loop caller did not finish")
    flat = sorted((e for log in logs for e in log), key=lambda e: e[1])
    n = len(flat)
    done, outcome, idx, dist, queue_ms = _empty(n, k)
    rows = np.array([e[0] for e in flat], np.int64)
    sub = np.array([e[1] for e in flat])
    for j, e in enumerate(flat):
        done[j], outcome[j], queue_ms[j] = e[2], e[3], e[6]
        if e[4] is not None:
            idx[j], dist[j] = e[4], e[5]
    return Record(rows, sub.copy(), sub, done, outcome, idx, dist, queue_ms,
                  t0, seconds)


def percentile_ms(rec: Record, pct: float, deadline_ms: float) -> float:
    """The ``pct`` percentile of every request's latency from due time,
    misses ranked above every answer (``MISS_DEADLINES`` deadlines, or
    twice the slowest answer); nearest rank, so it is a value some request
    had."""
    lat = rec.latency_ms()
    ok = ~np.isnan(lat)
    miss = max(MISS_DEADLINES * deadline_ms,
               2.0 * float(np.max(lat[ok])) if ok.any() else 0.0)
    vals = np.where(ok, lat, miss)
    return float(np.percentile(vals, pct, method="inverted_cdf"))


def goodput_qps(rec: Record, deadline_ms: float) -> float:
    """Requests answered ``ok`` within the deadline, per window second."""
    lat = rec.latency_ms()
    return float(np.sum(lat <= deadline_ms)) / rec.seconds


def lateness_ms(rec: Record) -> dict:
    """How late the generator submitted: p50, p99 and max of submit - due."""
    late = (rec.submit - rec.due) * 1e3
    late = late[~np.isnan(late)]
    if late.size == 0:
        return {"p50": 0.0, "p99": 0.0, "max": 0.0}
    return {"p50": float(np.percentile(late, 50)),
            "p99": float(np.percentile(late, 99)),
            "max": float(np.max(late))}


def outcome_counts(rec: Record) -> dict:
    out: dict = {}
    for o in rec.outcome:
        out[o] = out.get(o, 0) + 1
    return out
