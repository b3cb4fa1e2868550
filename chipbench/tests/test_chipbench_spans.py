"""The program's spans against the device: hand-made events, a slice of
a trace recorded on the chip, and a whole traced run on the CPU."""
import json
import os
import shutil

import pytest

from chipbench import spans
from test_chipbench_harness import REPO, _run, root  # noqa: F401

DEV = "/device:TPU:0"
CLIENT, BATCHER, RUNTIME = 0, 1, 2


def _events():
    """A 1000 ns window: one batch on the batcher thread.  Its traversal
    is one module (a ``while`` and an op nested in it), launched inside
    the ``traversal`` span; its rerank two modules, one launched through
    a runtime thread the flow ids lead back from.  One more traversal
    execution was launched before the trace began."""
    return {
        "window": (0.0, 1000.0),
        "spans": [
            (BATCHER, -50.0, 100.0, "wait"),
            (BATCHER, 100.0, 900.0, "batch"),
            (BATCHER, 110.0, 130.0, "pad"),
            (BATCHER, 130.0, 600.0, "dispatch"),
            (BATCHER, 140.0, 200.0, "traversal"),
            (BATCHER, 200.0, 260.0, "rerank"),
            (BATCHER, 600.0, 700.0, "fetch"),
            (BATCHER, 700.0, 800.0, "resolve"),
            (BATCHER, 900.0, 1100.0, "wait"),
            (CLIENT, 820.0, 860.0, "gc"),
        ],
        # run_id -> the host event that carries it
        "launches": {"1": (BATCHER, 145.0, 146.0),
                     "2": (RUNTIME, 205.0, 215.0),
                     "3": (BATCHER, 250.0, 251.0),
                     "4": (CLIENT, 300.0, 301.0)},
        "flows_out": {"f2": (BATCHER, 202.0, 203.0)},
        "flows_in": {RUNTIME: [(204.0, 216.0, "f2")]},
        "modules": [
            (DEV, -100.0, 20.0, "jit__best_first_impl(11)", "0"),
            (DEV, 150.0, 350.0, "jit__best_first_impl(11)", "1"),
            (DEV, 360.0, 400.0, "jit_gather(12)", "2"),
            (DEV, 400.0, 450.0, "jit_add(13)", "3"),
            (DEV, 450.0, 460.0, "jit_add(15)", "4"),
            (DEV, 1000.0, 1200.0, "jit_brute_force(14)", "5"),
        ],
        "ops": [
            (DEV, -100.0, 20.0),
            (DEV, 150.0, 350.0),  # the traversal's while
            (DEV, 160.0, 200.0),  # an op nested in it
            (DEV, 360.0, 400.0),
            (DEV, 400.0, 450.0),
            (DEV, 450.0, 460.0),
            (DEV, 1000.0, 1200.0),  # after the window: clipped away
        ],
    }


def test_stages_count_nested_operations_once():
    r = spans.reduce(_events())
    # the execution launched before the trace takes its program's stage;
    # one launched outside any stage span, of a program seen nowhere
    # else, has none
    assert r["stage_s"] == {"traversal": pytest.approx(220e-9),
                            "rerank": pytest.approx(90e-9),
                            None: pytest.approx(10e-9)}
    assert r["busy_s"] == pytest.approx(sum(r["stage_s"].values()))


def test_idle_time_is_named_by_the_batcher_span_around_it():
    r = spans.reduce(_events())
    # idle [20, 150), [350, 360) and [460, 1000), under the innermost span
    assert r["idle_s"] == {
        "wait": pytest.approx(180e-9), "batch": pytest.approx(110e-9),
        "pad": pytest.approx(20e-9), "dispatch": pytest.approx(160e-9),
        "traversal": pytest.approx(10e-9), "fetch": pytest.approx(100e-9),
        "resolve": pytest.approx(100e-9)}
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_host_seconds_per_span_are_clipped_to_the_window():
    r = spans.reduce(_events())
    assert r["host_s"]["wait"] == pytest.approx(200e-9)
    assert r["host_s"]["pad"] + r["host_s"]["fetch"] == pytest.approx(120e-9)
    assert r["host_s"]["gc"] == pytest.approx(40e-9)


def test_innermost_segments_of_nested_spans():
    segs = spans._innermost([(0, 10, "a"), (2, 5, "b"), (3, 4, "c"),
                             (6, 8, "d")])
    assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"),
                    (5, 6, "a"), (6, 8, "d"), (8, 10, "a")]


def test_a_trace_without_program_spans_reads_as_nothing():
    ev = _events()
    ev["spans"] = []
    r = spans.reduce(ev)
    assert r["idle_s"] == {} and r["host_s"] == {}
    assert set(r["stage_s"]) == {None}
    assert spans.reduce(dict(ev, window=None)) is None


def test_a_recorded_chip_trace_names_its_stages_and_idle_time():
    """22 ms of a trace recorded on one TPU v5e (deep1m-inf.steady, seed
    3013000201): the end of one batch (its rerank's modules, the answer
    fetched and handed back), 7.1 ms with no request queued, then the next
    batch (pad, the embed launched op by op, the first 2 ms of the
    best-first ``while``).  Cut to the events the reduction reads: the
    batcher's spans, the module executions and operations in the slice,
    and each execution's launch with the flow events that lead back from
    it to the batcher."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "spans_deep1m_inf_steady_22ms.json")
    with open(path) as f:
        ev = json.load(f)
    ev["flows_in"] = {int(k): v for k, v in ev["flows_in"].items()}
    r = spans.reduce(ev)
    w0, w1 = ev["window"]
    assert r["window_s"] == pytest.approx(21.884782e-3)
    # every operation falls under a stage, the while and what it holds once
    ops = sum(min(e, w1) - max(s, w0) for _, s, e in ev["ops"]
              if min(e, w1) > max(s, w0)) / 1e9
    assert r["busy_s"] == pytest.approx(sum(r["stage_s"].values()), rel=1e-12)
    assert r["busy_s"] == pytest.approx(3.476179e-3)
    assert ops == pytest.approx(4.480051e-3)
    assert set(r["stage_s"]) == {"embed", "traversal", "rerank"}
    assert r["stage_s"]["traversal"] == pytest.approx(1.9997e-3, abs=1e-7)
    assert r["stage_s"]["rerank"] == pytest.approx(1.4603e-3, abs=1e-7)
    # the idle time under fetch is the host's, the 7.1 ms under wait not
    idle = r["idle_s"]
    assert idle["fetch"] == pytest.approx(1.5638e-3, abs=1e-7)
    assert idle["wait"] == pytest.approx(7.106e-3, abs=1e-6)
    assert idle["embed"] == pytest.approx(7.5009e-3, abs=1e-7)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert spans.host_idle_s(r) == pytest.approx(
        sum(idle.values()) - idle["wait"] - idle[None])
    assert spans.host_idle_s(r) == pytest.approx(11.2787e-3, abs=1e-6)


PROGRAM_METRICS = {"server_host_ms": "program_span",
                   "dispatch_gap_max_ms": "program_span",
                   "gc_max_ms": "program_span",
                   "traversal_ms": "device_trace",
                   "host_idle_ms": "device_trace"}


def test_traced_run_reads_the_program_spans_and_counters(root):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for name, source in PROGRAM_METRICS.items():
        shutil.copy(os.path.join(REPO, "chipbench", "metrics", name + ".py"),
                    os.path.join(root, "chipbench", "metrics", name + ".py"))
        bench["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower", "source": source,
             "layer": "runtime", "moves": "p99_ms",
             "workloads": ["tiny.open"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    res = _run(root, "tiny.open", trace=True)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    # the program's spans lie in a CPU trace too; its device does not
    assert m["server_host_ms"]["value"] > 0
    assert m["gc_max_ms"]["value"] >= 0
    assert m["dispatch_gap_max_ms"]["value"] >= 0
    assert "traversal_ms" not in m and "host_idle_ms" not in m
