"""The load generator: timing from the due time, misses counted."""
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from chipbench import loadgen


class Answer:
    def __init__(self, outcome="ok", k=3):
        self.outcome = outcome
        self.idx = np.zeros((1, k), np.int32)
        self.dist = np.zeros((1, k), np.float32)
        self.queue_ms = 0.5


class Refused(Exception):
    reason = "capacity"


class FakeSystem:
    """Answers after ``service_s``; the request numbered ``stall_at`` holds
    the submitting thread for ``stall_s``; ``plan`` maps request numbers
    to "reject", "shed", "error" or "lose"."""

    def __init__(self, service_s=0.002, stall_at=None, stall_s=0.0, plan=None):
        self.service_s = service_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.plan = plan or {}
        self.n = 0

    def submit(self, q, deadline_ms):
        j = self.n
        self.n += 1
        if j == self.stall_at:
            time.sleep(self.stall_s)
        what = self.plan.get(j)
        if what == "reject":
            raise Refused()
        f = Future()
        if what == "lose":
            return f
        def finish():
            if what == "error":
                f.set_exception(RuntimeError("dispatch fault"))
            else:
                f.set_result(Answer("shed_expired" if what == "shed" else "ok"))
        threading.Timer(self.service_s, finish).start()
        return f

    @staticmethod
    def on_done(f, fn):
        f.add_done_callback(lambda _f: fn())

    @staticmethod
    def result(f, timeout):
        return f.result(timeout=timeout)


def _run(system, rate=200.0, seconds=0.5, grace_s=0.5):
    return loadgen.run_open(
        submit=system.submit, on_done=system.on_done, result=system.result,
        rejected=Refused, queries=np.zeros((7, 4), np.float32), rate=rate,
        seconds=seconds, seed=2 ** 31 + 9, deadline_ms=50.0, k=3,
        grace_s=grace_s)


def test_schedule_is_one_set_of_arrivals_in_a_seeded_order():
    a, ra = loadgen.open_schedule(100.0, 10.0, 1, 50)
    b, rb = loadgen.open_schedule(100.0, 10.0, 2, 50)
    assert len(a) == len(b) == 1000
    assert np.all(np.diff(a) > 0) and a[0] == 0.0 and a[-1] < 10.0
    gaps = [np.sort(np.append(np.diff(x), 10.0 - x[-1])) for x in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)
    assert not np.array_equal(a, b)
    assert ra.max() < 50


def test_latency_is_timed_from_the_due_time():
    # request 10 holds the generator 150 ms: every request due during the
    # stall waits for it, and its latency shows that wait
    rec = _run(FakeSystem(stall_at=10, stall_s=0.15))
    lat = rec.latency_ms()
    late = (rec.submit - rec.due) * 1e3
    assert np.all(lat >= late - 1e-6)
    stalled = (rec.due > rec.due[10]) & (rec.due < rec.due[10] + 0.1)
    assert stalled.sum() >= 5
    assert np.all(lat[stalled] > 40.0)
    assert loadgen.lateness_ms(rec)["max"] > 100.0


def test_every_miss_counts_and_ranks_above_every_answer():
    plan = {3: "reject", 5: "shed", 7: "error", 9: "lose"}
    rec = _run(FakeSystem(plan=plan), rate=100.0, seconds=0.2, grace_s=0.2)
    counts = loadgen.outcome_counts(rec)
    assert rec.n == 20
    assert counts == {"ok": 16, "rejected_capacity": 1, "shed_expired": 1,
                      "error": 1, "unanswered": 1}
    assert np.isnan(rec.latency_ms()[[3, 5, 7, 9]]).all()
    p99 = loadgen.percentile_ms(rec, 99, 50.0)
    p50 = loadgen.percentile_ms(rec, 50, 50.0)
    assert p99 >= loadgen.MISS_DEADLINES * 50.0 > p50
    assert loadgen.goodput_qps(rec, 50.0) == pytest.approx(16 / 0.2)


def test_closed_loop_sends_after_each_answer():
    system = FakeSystem(service_s=0.01)
    rec = loadgen.run_closed(
        submit=system.submit, on_done=system.on_done, result=system.result,
        rejected=Refused, queries=np.zeros((7, 4), np.float32), clients=4,
        seconds=0.3, seed=5, deadline_ms=50.0, k=3)
    assert 40 <= rec.n <= 4 * 30 + 4
    assert set(rec.outcome) == {"ok"}
    assert np.all(rec.latency_ms() >= 9.0)
