"""Discovery by name, and whole runs on the CPU at a tiny size.

A cell is added here the way a later change adds one: a configuration
file, a traffic file, a metric reader and entries in BENCHMARK.json, in a
directory of their own.  The runs skip the harness's look for a chip and
drive the rest of a run: set-up, the window through the runtime, the
comparison.  With the timed path broken underneath, ``correct`` must come
out false.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import control, harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {
    "name": "tiny-brute", "k": 10, "metric": "euclidean",
    "data": {"generator": "manifold", "n": 4096, "query_pool": 256,
             "params": {"d": 96}},
    "engine": "brute", "engine_cfg": {},
    "policy": {"capacity": 1024, "max_batch": 64, "flush_ms": 2.0},
    "fault_policy": {}, "deadline_ms": 5000.0,
    "correct": {"bad_answers": 0, "dist_err": 1.2e-6, "rank_gap": 1e-4},
}

TINY_INF = dict(
    TINY, name="tiny-inf", engine="infinity",
    data={"generator": "manifold", "n": 2048, "query_pool": 256,
          "params": {"d": 96}},
    engine_cfg={"q": "inf", "budget": 256, "rerank": 64, "train_steps": 40,
                "proj_sample": 256, "seed": "$seed"},
    policy={"capacity": 1024, "max_batch": 64, "flush_ms": 2.0,
            "budget": 256, "budget_floor": 256},
    fault_policy={"budget_floor": 256},
    # sound runs read recall 0.44-0.51 here, the traversal faults 0.001-0.11
    correct={"bad_answers": 0, "dist_err": 1.2e-6,
             "recall_at_10": {"min": 0.2}})

READER = '''"""Answered requests in the window."""


def read(run):
    return float(run["counters"]["completed"])
'''


@pytest.fixture()
def root(tmp_path):
    """A benchmark directory with tiny cells, added by files alone."""
    return make_root(tmp_path)


def make_root(tmp_path):
    cb = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        (cb / sub).mkdir(parents=True)
    (cb / "configs" / "tiny-brute.json").write_text(json.dumps(TINY))
    (cb / "configs" / "tiny-inf.json").write_text(json.dumps(TINY_INF))
    (cb / "traffic" / "poisson_tiny.json").write_text(
        json.dumps({"loop": "open", "rate": 150.0}))
    (cb / "traffic" / "poisson_fast.json").write_text(
        json.dumps({"loop": "open", "rate": 1000.0}))
    (cb / "traffic" / "closed_tiny.json").write_text(
        json.dumps({"loop": "closed", "clients": 4}))
    for name in ("queue_wait_ms", "batch_fill", "device_idle_pct"):
        shutil.copy(os.path.join(REPO, "chipbench", "metrics", name + ".py"),
                    cb / "metrics" / (name + ".py"))
    (cb / "metrics" / "answered.tiny.py").write_text(READER)
    bench = {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny-brute", "source": "test",
                     "file": "chipbench/configs/tiny-brute.json",
                     "reduced": [], "why": "test"},
                    {"name": "tiny-inf", "source": "test",
                     "file": "chipbench/configs/tiny-inf.json",
                     "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "tiny.open", "config": "tiny-brute",
             "traffic": "poisson_tiny", "chips": 1, "why": "test"},
            {"name": "tiny.fast", "config": "tiny-brute",
             "traffic": "poisson_fast", "chips": 1, "why": "test"},
            {"name": "tiny-inf.fast", "config": "tiny-inf",
             "traffic": "poisson_fast", "chips": 1, "why": "test"},
            {"name": "tiny.closed", "config": "tiny-brute",
             "traffic": "closed_tiny", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "goodput_qps", "unit": "req/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "p99_ms", "unit": "ms", "better": "lower", "bound": 0.2,
             "source": "host_clock", "workloads": ["tiny.open"]},
            {"name": "recall_at_10", "unit": "ratio", "better": "higher",
             "bound": 0.01, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "queue_wait_ms", "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "runtime", "moves": "p99_ms"},
            {"name": "batch_fill", "unit": "req", "better": "higher",
             "source": "program_counter", "layer": "runtime",
             "moves": "goodput_qps", "workloads": ["tiny.closed"]},
            {"name": "answered.tiny", "unit": "req", "better": "higher",
             "source": "program_counter", "layer": "runtime",
             "moves": "goodput_qps", "workloads": ["tiny.open"]},
            {"name": "device_idle_pct", "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "device",
             "moves": "goodput_qps", "workloads": ["tiny.open"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def _run(root, workload, seed=2 ** 31 + 3, trace=False, **kw):
    return harness.run_cell(root, workload, seed, 1.0, trace,
                            t_process=time.monotonic(),
                            require=lambda chips: harness.device_info(), **kw)


def test_cells_configs_traffic_and_readers_are_found_by_name(root):
    bench = harness.load_benchmark(root)
    spec = harness.find_cell(bench, root, "tiny.open")
    assert spec["config"]["engine"] == "brute"
    assert spec["traffic"] == {"loop": "open", "rate": 150.0}
    assert [m["name"] for m in spec["end_to_end"]] == [
        "goodput_qps", "p99_ms", "recall_at_10", "setup_s"]
    assert [m["name"] for m in spec["per_layer"]] == [
        "queue_wait_ms", "answered.tiny", "device_idle_pct"]
    closed = harness.find_cell(bench, root, "tiny.closed")
    assert "p99_ms" not in [m["name"] for m in closed["end_to_end"]]
    assert [m["name"] for m in closed["per_layer"]] == ["batch_fill"]
    read = harness.load_reader(root, "answered.tiny")
    assert read({"counters": {"completed": 7}}) == 7.0
    with pytest.raises(KeyError):
        harness.find_cell(bench, root, "no.such")


def test_the_repository_benchmark_names_files_that_exist():
    bench = harness.load_benchmark(REPO)
    for cell in bench["workloads"]:
        spec = harness.find_cell(bench, REPO, cell["name"])
        assert spec["end_to_end"] and spec["per_layer"]
        assert "setup_s" in [m["name"] for m in spec["end_to_end"]]
        for m in spec["per_layer"]:
            assert callable(harness.load_reader(REPO, m["name"]))


def test_open_run_on_the_cpu_is_correct_and_reports_every_key(root):
    res = _run(root, "tiny.open")
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] == 150 and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"goodput_qps", "p99_ms", "recall_at_10", "setup_s"}
    assert m["recall_at_10"]["value"] == pytest.approx(1.0)
    assert m["goodput_qps"]["value"] == pytest.approx(150.0)
    assert res["device"]["memory_peak_bytes"] >= 0
    assert res["load"]["compiles_in_window"] == 0
    assert res["checks"]["bad_answers"]["value"] == 0


def test_traced_closed_run_reads_the_per_layer_metrics(root):
    res = _run(root, "tiny.closed", trace=True)
    assert res["correct"] is True, res["checks"]
    # no TPU plane in a CPU trace: the device metric finds nothing to read
    assert set(res["metrics"]) == {"batch_fill"}
    assert 1.0 <= res["metrics"]["batch_fill"]["value"] <= 4.0
    assert res["device"]["window_s"] > 0


def test_traced_open_run_reads_the_client_tail_per_layer(root):
    name = "p99_ms.deep1m-inf.steady"
    shutil.copy(os.path.join(REPO, "chipbench", "metrics", name + ".py"),
                os.path.join(root, "chipbench", "metrics", name + ".py"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append(
        {"name": name, "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "runtime", "moves": "p99_ms",
         "workloads": ["tiny.open"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    res = _run(root, "tiny.open", trace=True)
    assert res["correct"] is True, res["checks"]
    tail = res["metrics"][name]["value"]
    assert tail == res["load"]["e2e"]["p99_ms"] and tail > 0


def _alter_answer(system):
    inner = system.server.index.search

    def search(Q, k=10, **kw):
        idx, dist, comps = inner(Q, k=k, **kw)
        return (idx.at[:, 0].set((idx[:, 0] + 1) % system.server.corpus.shape[0]),
                dist, comps)

    system.server.index.search = search


def _drop_half_batch(system):
    """The runtime's batch is answered for its first half only; the rest
    get the first half's answers."""
    inner = system.server.query

    def query(batch, *a, **kw):
        h = max(1, len(batch) // 2)
        res = inner(batch[:h], *a, **kw)
        pick = np.arange(len(batch)) % h
        return res._replace(idx=res.idx[pick], dist=res.dist[pick],
                            comparisons=res.comparisons[pick])

    system.server.query = query


@pytest.mark.parametrize("cell", ["tiny.fast", "tiny-inf.fast"])
@pytest.mark.parametrize("fault", [None, _alter_answer, _drop_half_batch],
                         ids=["sound", "answer_altered", "half_batch_left_out"])
def test_a_broken_timed_path_is_not_correct(root, cell, fault):
    # 1000 req/s for a second, so that the runtime forms batches of several
    res = _run(root, cell, program_hook=fault)
    assert res["correct"] is (fault is None), res["checks"]
    if fault is not None:
        assert res["checks"]["dist_err"]["value"] > TINY["correct"]["dist_err"]


@pytest.mark.parametrize("fault", ["negated_embedding", "cut_budget"])
def test_a_traversal_that_misses_neighbours_is_not_correct(root, fault):
    # valid ids with their true distances: only the recall floor sees it
    res = _run(root, "tiny-inf.fast", program_hook=control.FAULTS[fault][0])
    assert res["correct"] is False, res["checks"]
    c = res["checks"]
    assert c["bad_answers"]["value"] == 0
    assert c["dist_err"]["value"] <= c["dist_err"]["limit"]
    assert c["recall_at_10"]["value"] < c["recall_at_10"]["limit"]


def test_the_control_is_not_correct(root):
    ok = _run(root, "tiny.open")
    res = _run(root, "tiny.open", program_hook=control.install,
               config_override=control.CONFIG_OVERRIDE)
    assert res["correct"] is False
    err, base = res["checks"]["dist_err"]["value"], ok["checks"]["dist_err"]["value"]
    assert err > TINY["correct"]["dist_err"] and err > 3 * base


def test_run_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bench = harness.load_benchmark(REPO)
    cell = bench["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert p.stdout.strip() == ""


def test_the_run_waits_for_the_trace_however_long_its_stop_takes():
    import threading

    holder: dict = {}

    def stop_slowly():
        time.sleep(1.0)
        holder["path"] = "trace.xplane.pb"

    th = threading.Thread(target=stop_slowly)
    th.start()
    assert harness.wait_for_trace(th, holder) == "trace.xplane.pb"
    assert not th.is_alive()


def test_a_trace_that_is_not_written_raises_naming_the_seconds_waited(
        tmp_path, monkeypatch):
    class ProfilerBusy:
        def __init__(self, logdir):
            pass

        def start(self):
            raise RuntimeError("profiler busy")

    monkeypatch.setattr(harness.tracing, "Trace", ProfilerBusy)
    th, holder = harness._traced(str(tmp_path), 0.0, 0.0)
    th.start()
    with pytest.raises(RuntimeError, match=r"no trace written after waiting "
                                           r"\d+\.\d s .*profiler busy"):
        harness.wait_for_trace(th, holder)
