"""One run of one cell: set up, measure a window, judge, report.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``chipbench/traffic/<traffic>.json`` and, for each
per-layer metric, ``chipbench/metrics/<name>.py`` with a ``read(run)`` that
returns a number or None.  ``run_cell`` returns the result line's object;
``run.py`` is the command line around it.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import threading
import time
from typing import Callable, Optional

import numpy as np

from chipbench import compare, datagen, loadgen, tracing

#: the traced part of a ``--trace 1`` window: this long, from this share
#: of the window on
TRACE_SECONDS = 2.0
TRACE_FROM = 0.25
PROGRAM_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# --------------------------------------------------------------- discovery
def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def traffic_path(root: str, name: str) -> str:
    return os.path.join(root, "chipbench", "traffic", f"{name}.json")


def metric_path(root: str, name: str) -> str:
    return os.path.join(root, "chipbench", "metrics", f"{name}.py")


def find_cell(bench: dict, root: str, workload: str) -> dict:
    """The cell ``workload`` with its configuration, traffic and metrics."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(traffic_path(root, cell["traffic"])) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in reported)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def load_reader(root: str, name: str) -> Callable:
    """``read`` of ``chipbench/metrics/<name>.py``."""
    path = metric_path(root, name)
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------- devices
def require_tpu(chips: int = 1) -> dict:
    """The devices JAX found; raises unless they are TPUs, ``chips`` or
    more (after ``chip_smoke.require_tpu``)."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise RuntimeError(
            f"no TPU found: JAX reports platform {d0.platform!r} "
            f"({d0.device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise RuntimeError(f"need {chips} TPU chips, JAX reports {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def place_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at the program's place for it
    (``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``),
    caching every program, however fast it compiled."""
    import jax
    from repro.launch.compile_cache import place_compile_cache as place

    where = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileCounter:
    """Counts the programs the backend compiles while it is armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == PROGRAM_COMPILE_EVENT:
            self.count += 1


# -------------------------------------------------------------------- run
def concurrency(traffic: dict) -> int:
    """The most requests the traffic can have outstanding at once."""
    if traffic["loop"] == "closed":
        return int(traffic["clients"])
    return 1 << 30


def drive(system, traffic: dict, pool: np.ndarray, *, seconds: float,
          seed: int, deadline_ms: float, k: int, on_start=None):
    kw = dict(submit=system.submit, on_done=system.on_done,
              result=system.result, rejected=system.rejected, queries=pool,
              seconds=seconds, seed=seed, deadline_ms=deadline_ms, k=k,
              on_start=on_start)
    if traffic["loop"] == "open":
        return loadgen.run_open(rate=float(traffic["rate"]), **kw)
    if traffic["loop"] == "closed":
        return loadgen.run_closed(clients=int(traffic["clients"]), **kw)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def _traced(logdir: str, t_from: float, t_len: float):
    """A thread that traces ``t_len`` seconds from ``t_from`` seconds
    after it starts; returns (thread, holder of the trace's path, or of
    the error that stopped the thread)."""
    holder: dict = {}

    def body():
        try:
            time.sleep(t_from)
            tr = tracing.Trace(logdir)
            tr.start()
            time.sleep(t_len)
            holder["path"] = tr.stop()
        except Exception as e:  # handed to the run, which raises it
            holder["error"] = e

    th = threading.Thread(target=body, name="chipbench-trace", daemon=True)
    return th, holder


def wait_for_trace(thread: threading.Thread, holder: dict) -> str:
    """Waits for the trace thread until it has written the trace (the
    profiler's stop takes longer the more device events the window holds:
    ~118 us each on a v5e host, and four chips write four planes); returns
    the trace's path, or raises naming the seconds waited."""
    t0 = time.monotonic()
    thread.join()
    if "path" not in holder:
        raise RuntimeError(
            f"no trace written after waiting {time.monotonic() - t0:.1f} s "
            f"for the trace thread: {holder.get('error')!r}")
    return holder["path"]


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_process: float,
             require: Callable = require_tpu,
             program_hook: Optional[Callable] = None,
             config_override: Optional[dict] = None) -> dict:
    """One run; returns the result line's object (``checks`` last).

    ``require`` checks the devices (a test passes a stand-in);
    ``program_hook(system)`` may replace parts of the built system (the
    control and the fault tests do); ``config_override`` replaces keys of
    the configuration (the control's, which needs no engine build)."""
    import jax

    bench = load_benchmark(root)
    spec = find_cell(bench, root, workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    config = dict(config, **(config_override or {}))
    place_compile_cache(root)
    device = require(int(cell["chips"]))
    k = int(config["k"])
    deadline_ms = float(config["deadline_ms"])
    data = config["data"]
    n = int(data["n"])
    shards = int(data.get("shards", 1))
    mesh = (None if shards == 1
            else datagen.corpus_mesh(n, shards, int(cell["chips"])))

    X = datagen.make(data["generator"], seed, 0, n, mesh=mesh,
                     **data.get("params", {}))
    pool = np.asarray(datagen.make(data["generator"], seed, 1,
                                   int(data["query_pool"]),
                                   **data.get("params", {})))
    jax.block_until_ready(X)

    from chipbench import system as system_lib

    system = system_lib.System(config, X, seed)
    if program_hook is not None:
        program_hook(system)
    buckets = system.warm(pool, concurrency(traffic))
    stages = system.build_stages()
    counter = CompileCounter()
    system.start()

    trace_thread = holder = None
    if trace:
        logdir = os.path.join(root, "chipbench", "out", "trace",
                              f"{workload}-{seed}")
        trace_thread, holder = _traced(logdir, TRACE_FROM * seconds,
                                 min(TRACE_SECONDS, seconds * (1 - TRACE_FROM)))
    marks: dict = {}

    def on_start():
        marks["setup_end"] = time.monotonic()
        counter.armed = True
        if trace_thread is not None:
            trace_thread.start()

    rec = drive(system, traffic, pool, seconds=seconds, seed=seed,
                deadline_ms=deadline_ms, k=k, on_start=on_start)
    counter.armed = False
    trace_path = None
    if trace_thread is not None:
        trace_path = wait_for_trace(trace_thread, holder)
    counters = system.counters()
    system.stop()
    peak = memory_peak_bytes()
    system.close()
    del system
    gc.collect()

    ok = rec.ok()
    verdict = compare.judge(X, pool, rec.qrow[ok], rec.idx[ok],
                            rec.dist[ok], k=k, checks=config["correct"])
    setup_s = marks["setup_end"] - t_process
    e2e_values = {
        "goodput_qps": loadgen.goodput_qps(rec, deadline_ms),
        "p50_ms": loadgen.percentile_ms(rec, 50, deadline_ms),
        "p99_ms": loadgen.percentile_ms(rec, 99, deadline_ms),
        "recall_at_10": verdict["recall_at_10"],
        "setup_s": setup_s,
    }
    out_dev = dict(device, memory_peak_bytes=peak)
    metrics = {}
    breakdown = None
    if not trace:
        for m in spec["end_to_end"]:
            v = e2e_values[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        reduced = tracing.reduce(tracing.read_events(trace_path))
        run = {
            "cell": cell, "config": config, "traffic": traffic,
            "counters": counters, "build_s": stages,
            "queue_ms": rec.queue_ms[ok], "e2e": e2e_values,
            "trace": reduced, "trace_path": trace_path,
            "device_kind": device["kind"],
            "n": n, "d": int(X.shape[1]), "k": k,
        }
        for m in spec["per_layer"]:
            v = load_reader(root, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out_dev["busy_s"] = reduced["busy_s"]
        out_dev["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    attempted = rec.n
    counts = loadgen.outcome_counts(rec)
    failed = counts.get("error", 0) + counts.get("unanswered", 0)
    result = {
        "correct": verdict["correct"] and failed == 0 and bool(ok.any()),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": out_dev,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["load"] = {
        "outcomes": counts, "lateness_ms": loadgen.lateness_ms(rec),
        "compiles_in_window": counter.count, "buckets": buckets,
        "batches": int(counters.get("batches", 0)), "build_s": stages,
        "e2e": e2e_values,
    }
    result["checks"] = verdict["checks"]
    return result


def report(result: dict) -> None:
    """The compared numbers on standard error, last; the result line on
    standard output, last."""
    print(json.dumps(result["load"]), file=sys.stderr)
    for name, c in result["checks"].items():
        sign = ">=" if c.get("is") == "min" else "<="
        print(f"check {name}: {c['value']!r} (limit {sign} {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
