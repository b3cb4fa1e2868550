"""The trace reduction, on hand-made events and on a trace the profiler
recorded."""
import pytest

from chipbench import tracing

DEV = "/device:TPU:0"
HOST = "/host:CPU"
OPS, MODS = tracing.OPS_LINE, tracing.MODULES_LINE


def ev(plane, line, name, start, dur, module=""):
    return (plane, line, name, float(start), float(dur), module)


def _events():
    return [
        ev(HOST, "python", tracing.WINDOW_SPAN, 0, 1000),
        ev(HOST, "python", "chipbench.query", 90, 300, "64"),
        ev(HOST, "python", "chipbench.query", 600, 100, "16"),
        # half of this batch lies after the window's end
        ev(HOST, "python", "chipbench.query", 950, 100, "8"),
        # two overlapping ops, one straddling the window's end
        ev(DEV, OPS, "fusion.1", 100, 200, "jit_a"),
        ev(DEV, OPS, "fusion.2", 150, 250, "jit_a"),
        ev(DEV, OPS, "dot.3", 900, 300, "jit_b"),
        ev(DEV, MODS, "jit_a(17)", 100, 250),
        ev(DEV, MODS, "jit_b(4)", 900, 300),
        ev(DEV, MODS, "jit_a(17)", 1500, 10),  # after the window
    ]


def test_busy_is_the_union_of_op_intervals_in_the_window():
    r = tracing.reduce(_events())
    assert r["window_s"] == pytest.approx(1000e-9)
    # [100, 400) and [900, 1000) after clipping
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["devices"] == 1


def test_modules_are_summed_by_name_without_their_id():
    r = tracing.reduce(_events())
    # jit_b runs 900-1200: only its first 100 ns lie in the window
    assert r["modules"] == {"jit_a": [pytest.approx(250e-9), 1],
                            "jit_b": [pytest.approx(100e-9), 1]}
    secs, n = tracing.module_seconds(r, [r"^jit_a$"])
    assert (secs, n) == (pytest.approx(250e-9), 1)


def test_batches_are_counted_by_their_share_in_the_window():
    r = tracing.reduce(_events())
    assert r["queries"] == [[1.0, 64], [1.0, 16], [pytest.approx(0.5), 8]]
    assert r["batches"] == pytest.approx(2.5)


def test_top_ops_and_idle_gaps_are_named():
    r = tracing.reduce(_events())
    names = [n for n, _ in r["device_ops"]]
    assert names == ["fusion.2", "fusion.1", "dot.3"]  # 250, 200, 100 ns
    gaps = r["idle_gaps"]
    # [400, 900) is the longest gap: its middle lies in the 600-700 query
    assert gaps[0][0] == "chipbench.query"
    assert gaps[0][1] == pytest.approx(500e-9)
    assert gaps[1] == ["no benchmark span", pytest.approx(100e-9)]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tracing.reduce([e for e in _events() if e[2] != tracing.WINDOW_SPAN])


def test_reading_a_cpu_trace_keeps_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tr = tracing.Trace(str(tmp_path))
    tr.start()
    with jax.profiler.TraceAnnotation("chipbench.query"):
        f(x).block_until_ready()
    path = tr.stop()
    events = tracing.read_events(path)
    names = {e[2] for e in events}
    assert {tracing.WINDOW_SPAN, "chipbench.query"} <= names
    r = tracing.reduce(events)
    assert r["window_s"] > 0 and r["devices"] == 0


def test_a_recorded_chip_trace_reduces_to_the_scan_metrics():
    """1.5 ms of a trace recorded on one TPU v5e (deep10m-brute.steady,
    seed 3000000001): the end of the corpus pad and the start of the scan
    loop inside one ``jit_brute_force`` call, batch of 7 padded to 8.  The
    window span is cut to the slice and the operations' names to their HLO
    instruction."""
    import json
    import os

    from chipbench import harness

    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_deep10m_brute_1500us.json")
    with open(path) as f:
        events = [tuple(e) for e in json.load(f)]
    r = tracing.reduce(events)
    assert r["window_s"] == pytest.approx(1.5e-3)
    assert r["devices"] == 1
    assert 0.99 * r["window_s"] < r["busy_s"] <= r["window_s"]
    assert r["modules"] == {"jit_brute_force": [pytest.approx(1.5e-3), 1]}
    # one 73.9 ms query span, 1.5 ms of it inside the window
    assert r["queries"] == [[pytest.approx(1.5 / 73.880527), 7]]
    assert [n for n, _ in r["device_ops"][:2]] == ["%while.15", "%pad.2"]
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    run = {"trace": r, "n": 10_000_000, "d": 96, "k": 10,
           "device_kind": "TPU v5 lite"}
    # device time per batch is the query span's length
    scan_ms = harness.load_reader(root, "scan_ms")(run)
    assert scan_ms == pytest.approx(73.880527, rel=1e-6)
    roof = harness.load_reader(root, "scan_roofline")(run)
    assert 0 < roof < 100
    assert roof == pytest.approx(100 * (3.84e9 + 7 * 96 * 4 + 7 * 80) / 819e9
                                 / 73.880527e-3, rel=1e-6)
