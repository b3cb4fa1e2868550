"""The program's own spans in a ``--trace 1`` run, against the device.

``core/telemetry.span`` opens a ``jax.profiler.TraceAnnotation``, so the
program's spans (``wait``, ``batch``, ``pad``, ``dispatch``, ``fetch``,
``resolve``, ``embed``, ``traversal``, ``rerank``, ``scan``, ``gc``) lie
on the trace's ``/host:CPU`` plane, one line per thread, on the clock of
the device's operations.  ``read`` keeps them, the device's operations
and the host's launches; ``reduce`` turns those into what the readers of
``chipbench/metrics`` take, all clipped to the window's
``chipbench.trace`` span:

* ``stage_s``: device seconds per engine stage (``embed``, ``traversal``,
  ``rerank``, ``scan``, and ``None`` for none), each the union of its
  operations' intervals, so an operation nested in a ``while`` counts
  once.  An operation belongs to the module execution around it on the
  device (line ``XLA Modules``), and the execution to the host launch
  that enqueued it: the host event that carries the same ``run_id``
  (``DoEnqueueProgram`` on a TPU), followed back through the profiler's
  flow ids (``_c`` of an event around it, ``_p`` of the event that
  started it) to the thread that called, and there to the innermost
  stage span around the call.  An execution whose launch lies in no
  recorded stage span (a span open when the profiler started is not
  recorded) takes the stage of the other executions of its program
  where they all agree;
* ``busy_s``: the union of all operations' intervals;
* ``idle_s``: device-idle seconds by the innermost program span the
  batcher thread (the line that holds ``wait``) was in, ``None`` where it
  was in none;
* ``host_s``: host seconds per program span name, over all threads.

A trace without the program's spans (a program that predates them)
reduces to device time under no stage and no idle or host time, and the
readers then return None.
"""
from __future__ import annotations

import bisect
from typing import Iterable, Optional

from chipbench import tracing

STAGES = ("embed", "traversal", "rerank", "scan")
PROGRAM_SPANS = STAGES + ("wait", "batch", "resolve", "pad", "dispatch",
                          "fetch", "gc")
WAIT = "wait"
#: the stat that joins a module execution on the device to its launch
LAUNCH_STAT = "run_id"
#: flow ids: an event with ``FLOW_OUT`` starts work that an event with
#: the same value under ``FLOW_IN`` carries on, maybe on another thread
FLOW_OUT, FLOW_IN = "_p", "_c"


def read(path: str) -> dict:
    """The window, the program's spans, the host's launches and flows, and
    the device's module executions and operations, of one trace (ns)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = {"window": None, "spans": [], "launches": {}, "flows_out": {},
           "flows_in": {}, "modules": [], "ops": []}
    for plane in pd.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if line.name == tracing.OPS_LINE:
                        out["ops"].append((plane.name, s, e))
                    elif line.name == tracing.MODULES_LINE:
                        rid = dict(ev.stats).get(LAUNCH_STAT)
                        out["modules"].append((plane.name, s, e, ev.name,
                                               str(rid)))
        elif plane.name == tracing.HOST_PLANE:
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if ev.name == tracing.WINDOW_SPAN:
                        out["window"] = out["window"] or (s, e)
                    elif ev.name in PROGRAM_SPANS:
                        out["spans"].append((i, s, e, ev.name))
                    for k, v in ev.stats:
                        if k == LAUNCH_STAT:
                            old = out["launches"].get(str(v))
                            if old is None or s < old[1]:
                                out["launches"][str(v)] = (i, s, e)
                        elif k == FLOW_OUT:
                            out["flows_out"][str(v)] = (i, s, e)
                        elif k == FLOW_IN:
                            out["flows_in"].setdefault(i, []).append(
                                (s, e, str(v)))
    return out


def _union(intervals: Iterable) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _length(intervals: Iterable) -> float:
    return float(sum(e - s for s, e in intervals))


def _innermost(spans: Iterable) -> list:
    """``(start, end, name)`` spans of one thread, which nest, as disjoint
    segments each named by the innermost span over it."""
    out, stack, t = [], [], None
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            if t < end:
                out.append((t, end, nm))
            t = max(t, end)
        if stack and t < s:
            out.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    while stack:
        end, nm = stack.pop()
        if t < end:
            out.append((t, end, nm))
        t = max(t, end)
    return out


def _by_label(intervals: list, segments: list) -> dict:
    """Length of sorted, disjoint ``intervals`` under each segment's name
    (``None``: under none)."""
    out: dict = {}
    j = 0
    for s, e in intervals:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b = max(s, segments[k][0]), min(e, segments[k][1])
            if b > a:
                out[segments[k][2]] = out.get(segments[k][2], 0.0) + (b - a)
                covered += b - a
            k += 1
        if e - s > covered:
            out[None] = out.get(None, 0.0) + (e - s - covered)
    return out


def _caller(ev: dict, run_id: str) -> Optional[tuple]:
    """``(line, time)`` of the call that launched execution ``run_id``:
    from the host event that carries its ``run_id``, back along the flow
    that started each event (the innermost ``FLOW_IN`` event around it),
    to an event that no flow started.  None where the launch is not in
    the trace."""
    if run_id not in ev["launches"]:
        return None
    line, s, e = ev["launches"][run_id]
    for _ in range(16):
        around = [(ce - cs, fid)
                  for cs, ce, fid in ev["flows_in"].get(line, ())
                  if cs <= s and e <= ce]
        if not around or min(around)[1] not in ev["flows_out"]:
            return line, s
        line, s, e = ev["flows_out"][min(around)[1]]
    return None


def reduce(ev: dict) -> Optional[dict]:
    """Per-stage device seconds, busy and idle seconds, and host seconds
    per span, inside the window (see the module doc); None without one."""
    if ev["window"] is None:
        return None
    w0, w1 = ev["window"]
    by_line: dict = {}
    for line, s, e, name in ev["spans"]:
        by_line.setdefault(line, []).append((s, e, name))

    def stage_at(line: int, t: float) -> Optional[str]:
        inner = [(e - s, name) for s, e, name in by_line.get(line, ())
                 if name in STAGES and s <= t <= e]
        return min(inner)[1] if inner else None

    # each module execution's stage, through its launch where the trace
    # holds it inside a stage span, else through the other executions of
    # its program (a span open when the profiler started is not recorded)
    stage_of: dict = {}
    seen: dict = {}
    for plane, s, e, name, run_id in ev["modules"]:
        call = _caller(ev, run_id)
        stage = stage_at(*call) if call is not None else None
        if stage is not None:
            stage_of[run_id] = stage
            seen.setdefault(name, set()).add(stage)
    execs: dict = {}
    for plane, s, e, name, run_id in sorted(ev["modules"], key=lambda m: m[1]):
        if run_id not in stage_of and len(seen.get(name, ())) == 1:
            stage_of[run_id] = next(iter(seen[name]))
        execs.setdefault(plane, []).append((s, e, stage_of.get(run_id)))

    per_stage: dict = {}
    per_device: dict = {}
    for plane, s, e in ev["ops"]:
        mods = execs.get(plane, [])
        k = bisect.bisect_right(mods, (s, float("inf"), "")) - 1
        stage = mods[k][2] if k >= 0 and s < mods[k][1] else None
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        per_stage.setdefault(stage, []).append((s, e))
        per_device.setdefault(plane, []).append((s, e))
    stage_s = {k: _length(_union(v)) / 1e9 for k, v in per_stage.items()}
    busy = {p: _union(v) for p, v in per_device.items()}
    busy_s = (sum(_length(v) for v in busy.values()) / len(busy) / 1e9
              if busy else 0.0)

    idle_s: dict = {}
    batcher = [line for line, sp in by_line.items()
               if any(n == WAIT for _, _, n in sp)]
    if batcher and busy:
        segments = _innermost(by_line[batcher[0]])
        for merged in busy.values():
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
            for name, ns in _by_label(idle, segments).items():
                idle_s[name] = idle_s.get(name, 0.0) + ns / 1e9 / len(busy)

    host_s: dict = {}
    for line, s, e, name in ev["spans"]:
        inside = min(e, w1) - max(s, w0)
        if inside > 0:
            host_s[name] = host_s.get(name, 0.0) + inside / 1e9
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy_s,
            "stage_s": stage_s, "idle_s": idle_s, "host_s": host_s}


_CACHE: dict = {}


def of_run(run: dict) -> Optional[dict]:
    """``reduce`` of the trace the harness wrote for the run
    (``run["trace_path"]``), read once per trace; None where the run has
    none."""
    path = run.get("trace_path")
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = reduce(read(path))
    return _CACHE[path]


def host_idle_s(red: dict) -> float:
    """Device-idle seconds of ``reduce``'s result while the batcher was in
    a program span other than ``wait``: idle on the host, not on traffic."""
    return sum(s for name, s in red["idle_s"].items()
               if name not in (None, WAIT))


def per_batch_ms(run: dict, seconds: float) -> Optional[float]:
    """``seconds`` of the window in ms per batch the server dispatched in
    it (``chipbench.query`` spans by their share inside the window)."""
    batches = run["trace"]["batches"]
    return 1e3 * seconds / batches if batches > 0 else None

