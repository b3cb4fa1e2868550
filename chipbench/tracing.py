"""Device traces: take one, read it, and reduce it to numbers.

``Trace`` wraps the JAX profiler around part of a run's window, with the
Python tracer off.  ``read_events`` turns the ``.xplane.pb`` it writes into
plain tuples ``(plane, line, name, start_ns, dur_ns, arg)`` (``arg``: an
operation's XLA module, or a ``chipbench.query`` span's batch size), and
``reduce`` turns those into what the metrics read:

* ``window_s``: the length of the benchmark's own ``chipbench.trace`` host
  span, which brackets the traced part of the window;
* ``busy_s``: the union of the intervals in which an operation ran on a
  device (line ``XLA Ops`` of each ``/device:TPU:*`` plane), clipped to the
  window, averaged over the devices that ran anything;
* ``modules``: device seconds inside the window and executions that
  overlap it, per XLA module (line ``XLA Modules``), by the module's name
  without its ``(id)`` suffix, summed over every device plane: a program
  that runs on four chips counts four times its per-chip seconds;
* ``devices``: the number of devices that ran an operation in the window;
* ``batches``: the ``chipbench.query`` host spans (one per batch the server
  dispatched), each counted by the share of it that lies in the window, so
  that a batch across an edge counts as its device time does; and
  ``queries``: ``[share, batch size]`` of each;
* ``device_ops``: the ten operations that took the most device time;
* ``idle_gaps``: the ten longest gaps between device operations, each
  named by the innermost benchmark host span (``chipbench.*``) around its
  middle, or ``no benchmark span`` where none is.
"""
from __future__ import annotations

import glob
import os
import re
import time
from typing import Iterable, Optional

import jax

WINDOW_SPAN = "chipbench.trace"
QUERY_SPAN = "chipbench.query"
BATCH_STAT = "batch"
SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
_SUFFIX = re.compile(r"\(\d+\)$")


class Trace:
    """The profiler, on from ``start`` to ``stop``; host spans bracket it."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self._span = None
        self.t0 = self.t1 = None

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.t0 = time.monotonic()

    def stop(self) -> str:
        """Stop the profiler; returns the path of the trace it wrote."""
        self.t1 = time.monotonic()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {self.logdir}")
        return paths[-1]


def read_events(path: str, *, host_prefix: str = SPAN_PREFIX) -> list:
    """Events of the device planes, and the host spans whose name starts
    with ``host_prefix``, as ``(plane, line, name, start_ns, dur_ns, arg)``
    tuples."""
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not dev and not name.startswith(host_prefix):
                    continue
                arg = ""
                if dev and line.name == OPS_LINE:
                    arg = _stat(ev, "hlo_module") or ""
                elif name == QUERY_SPAN:
                    arg = _stat(ev, BATCH_STAT) or ""
                out.append((plane.name, line.name, name, float(ev.start_ns),
                            float(ev.duration_ns), arg))
    return out


def _stat(ev, key: str) -> Optional[str]:
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return None


def module_name(name: str) -> str:
    """An XLA module's name without its ``(id)`` suffix."""
    return _SUFFIX.sub("", name)


def _union(intervals: Iterable[tuple]) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce(events: list, *, top: int = 10) -> dict:
    """The numbers the metrics read from one trace (see the module doc):
    ``busy_s`` is a mean over the devices, ``modules`` a sum over them."""
    spans = [(s, s + d, n, m) for p, l, n, s, d, m in events
             if not DEVICE_PLANE.match(p)]
    win = [(s, e) for s, e, n, _ in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = win[0]
    dev_ops: dict = {}
    for p, l, n, s, d, m in events:
        if DEVICE_PLANE.match(p) and l == OPS_LINE:
            e = min(s + d, w1)
            s = max(s, w0)
            if e > s:
                dev_ops.setdefault(p, []).append((s, e, n, m))
    busy_per_dev = {}
    merged_per_dev = {}
    for p, ops in dev_ops.items():
        merged = _union((s, e) for s, e, _, _ in ops)
        merged_per_dev[p] = merged
        busy_per_dev[p] = sum(e - s for s, e in merged)
    busy_ns = (sum(busy_per_dev.values()) / len(busy_per_dev)
               if busy_per_dev else 0.0)

    modules: dict = {}
    for p, l, n, s, d, m in events:
        inside = min(s + d, w1) - max(s, w0)
        if DEVICE_PLANE.match(p) and l == MODULES_LINE and inside > 0:
            rec = modules.setdefault(module_name(n), [0.0, 0])
            rec[0] += inside / 1e9
            rec[1] += 1
    queries = []
    for s, e, n, m in spans:
        inside = min(e, w1) - max(s, w0)
        if n == QUERY_SPAN and inside > 0:
            queries.append([inside / (e - s), int(m) if m else 0])
    op_time: dict = {}
    for ops in dev_ops.values():
        for s, e, n, m in ops:
            op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]

    gaps = []
    host = [(s, e, n) for s, e, n, _ in spans if n != WINDOW_SPAN]
    for p, merged in merged_per_dev.items():
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    gaps.sort(key=lambda g: -g[0])
    idle = []
    for dur, mid in gaps[:top]:
        around = [(e - s, n) for s, e, n in host if s <= mid <= e]
        name = min(around)[1] if around else "no benchmark span"
        idle.append([name, dur / 1e9])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "devices": len(busy_per_dev),
        "modules": modules,
        "batches": sum(f for f, _ in queries),
        "queries": queries,
        "device_ops": [[n, s] for n, s in device_ops],
        "idle_gaps": idle,
    }


def module_seconds(reduced: dict, patterns: Iterable[str]) -> tuple:
    """(device seconds, executions) of the modules whose name matches any
    of the regular expressions ``patterns``."""
    regs = [re.compile(p) for p in patterns]
    secs, count = 0.0, 0
    for name, (s, c) in reduced["modules"].items():
        if any(r.search(name) for r in regs):
            secs += s
            count += c
    return secs, count
