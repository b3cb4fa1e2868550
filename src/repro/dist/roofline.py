"""Roofline accounting from optimized HLO text (DESIGN.md §Dry-run).

XLA's ``cost_analysis()`` counts a while-loop (scan) body ONCE — for scanned
layer stacks and microbatch loops that underestimates flops by the trip
count.  ``hlo_stats`` re-derives loop-aware flops/bytes by walking the HLO
call graph (entry -> fusions / while bodies) and multiplying every dot by
the product of enclosing trip counts.  Trip counts come from the canonical
XLA loop-condition shape ``compare(counter, constant(N)), direction=LT``;
loops whose bound cannot be recovered fall back to ``default_trip`` (the
microbatch count the caller knows).

``parse_collectives`` applies the same loop scaling to collective bytes so
the collective roofline term sees the per-step traffic, not one iteration's.

The hardware model is ``PEAKS``: published per-chip peaks keyed by the
``device_kind`` a JAX device reports.  A TPU kind missing from the table is
an error, never a silent default; a CPU has no entry (``device_peaks``
returns None) because its numbers are not a roofline of anything deployed.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip (per chip, not per host)."""

    bf16_flops: float  # dense bf16 matmul FLOP/s
    int8_ops: float  # dense int8 matmul OP/s
    hbm_bytes_s: float  # HBM bandwidth, bytes/s
    ici_bytes_s: float  # chip-to-chip interconnect, bytes/s
    source: str


#: device_kind -> peaks.  "TPU v5 lite" is what a TPU v5e reports.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_s=819e9,
        ici_bytes_s=1600e9 / 8,  # 1,600 Gbit/s
        source='Google Cloud documentation, "TPU v5e"',
    ),
}
#: the chip the serving path is built for: off-chip predictions (a CPU run,
#: the multi-pod dry-run) are made for it
TARGET_KIND = "TPU v5 lite"


def peaks(kind: str) -> ChipPeaks:
    """The peaks of ``kind``; raises for a kind not in ``PEAKS``."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {kind!r}; add it to "
            f"dist/roofline.PEAKS with its source (known: {sorted(PEAKS)})"
        ) from None


def device_peaks(device) -> Optional[ChipPeaks]:
    """Peaks of a JAX device: None off a TPU, an error for a TPU kind not in
    the table."""
    if device.platform != "tpu":
        return None
    return peaks(device.device_kind)


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->")
_CALL_RE = re.compile(r"(?:to_apply|calls)=%?([\w\.\-]+)")
_TRIP_HINT_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    total_bytes: float
    count: int
    loop_trip_counts: dict


@dataclasses.dataclass
class HloStats:
    flops: float
    bytes: float
    dot_count: int


def _shape_bytes(shape_str: str) -> float:
    """Total bytes of every typed array in an HLO shape string (handles
    tuples by summing members)."""
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_elems(shape_str: str) -> float:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return 0.0
    n = 1.0
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n


def _split_computations(hlo: str) -> dict[str, list[str]]:
    """Computation name -> its instruction lines."""
    comps: dict[str, list[str]] = {}
    current: Optional[str] = None
    for line in hlo.splitlines():
        stripped = line.strip()
        m = _COMP_RE.match(stripped)
        if m and stripped.endswith("{"):
            current = m.group(1)
            comps[current] = []
            continue
        if stripped.startswith("}"):
            current = None
            continue
        if current is not None and stripped:
            comps[current].append(line.rstrip())
    return comps


def _loop_bounds(comps: dict[str, list[str]], default_trip: int):
    """(body name -> trips, cond name -> body name) from while instructions."""
    trips: dict[str, int] = {}
    for lines in comps.values():
        for line in lines:
            if "while(" not in line:
                continue
            mc = re.search(r"condition=%?([\w\.\-]+)", line)
            mb = re.search(r"body=%?([\w\.\-]+)", line)
            if not (mc and mb):
                continue
            cond, body = mc.group(1), mb.group(1)
            hint = _TRIP_HINT_RE.search(line)  # XLA-annotated trip count
            if hint:
                trips[body] = int(hint.group(1))
            else:
                trips[body] = _trip_count_from_cond(comps.get(cond, []), default_trip)
    return trips


def _trip_count_from_cond(cond_lines: list[str], default_trip: int) -> int:
    """Recover N from the canonical ``i < N`` loop condition."""
    has_lt = any("direction=LT" in l for l in cond_lines)
    if not has_lt:
        return default_trip
    consts = []
    for l in cond_lines:
        m = re.search(r"=\s*[su]\d+\[\]\s+constant\((\d+)\)", l)
        if m:
            consts.append(int(m.group(1)))
    return consts[-1] if consts else default_trip


def _multipliers(comps, body_trips, default_trip: int, entry: str) -> dict[str, float]:
    """Computation -> product of enclosing loop trip counts (call graph walk
    from the entry; while bodies multiply by their trip count)."""
    mult: dict[str, float] = {entry: 1.0}
    stack = [entry]
    while stack:
        name = stack.pop()
        m = mult[name]
        for line in comps.get(name, []):
            callees = _CALL_RE.findall(line)
            mc = re.search(r"condition=%?([\w\.\-]+)", line)
            mb = re.search(r"body=%?([\w\.\-]+)", line)
            if mb:
                callees.append(mb.group(1))
            if mc:
                callees.append(mc.group(1))
            for callee in callees:
                factor = body_trips.get(callee, 1) if (mb and callee == mb.group(1)) else 1
                new = m * factor
                if mult.get(callee, 0.0) < new:
                    mult[callee] = new
                    stack.append(callee)
    return mult


def _entry_name(hlo: str) -> str:
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo, re.MULTILINE)
    return m.group(1) if m else "main"


def parse_collectives(hlo: str, *, default_trip: int = 1) -> CollectiveStats:
    comps = _split_computations(hlo)
    body_trips = _loop_bounds(comps, default_trip)
    mult = _multipliers(comps, body_trips, default_trip, _entry_name(hlo))
    bytes_by_kind: dict[str, float] = {}
    count = 0
    for name, lines in comps.items():
        m = mult.get(name, body_trips.get(name, 1))
        for line in lines:
            for kind in _COLLECTIVES:
                if f" {kind}(" in line:
                    shape_str = line.split(f" {kind}(")[0].split("=", 1)[-1]
                    b = _shape_bytes(shape_str) * m
                    bytes_by_kind[kind] = bytes_by_kind.get(kind, 0.0) + b
                    count += 1
                    break
    total = float(sum(bytes_by_kind.values()))
    return CollectiveStats(
        bytes_by_kind={k: int(v) for k, v in bytes_by_kind.items()},
        total_bytes=total,
        count=count,
        loop_trip_counts=dict(body_trips),
    )


def hlo_stats(hlo: str, *, default_trip: int = 1) -> HloStats:
    """Loop-aware flops (dots) and HBM bytes (instruction outputs)."""
    comps = _split_computations(hlo)
    body_trips = _loop_bounds(comps, default_trip)
    mult = _multipliers(comps, body_trips, default_trip, _entry_name(hlo))
    flops = 0.0
    bytes_total = 0.0
    dot_count = 0
    for name, lines in comps.items():
        m = mult.get(name, body_trips.get(name, 1))
        shapes: dict[str, str] = {}
        for line in lines:
            d = _DEF_RE.match(line)
            if d:
                shapes[d.group(1)] = d.group(2)
        for line in lines:
            d = _DEF_RE.match(line)
            if not d:
                continue
            out_shape, op = d.group(2), d.group(3)
            bytes_total += _shape_bytes(out_shape) * m
            if op != "dot":
                continue
            dot_count += 1
            # operands may carry type prefixes: dot(f32[16,32]{1,0} %a, ...)
            inner = re.search(r"dot\(([^)]*)\)", line)
            ops = re.findall(r"%([\w\.\-]+)", inner.group(1)) if inner else []
            lhs = ops[0] if ops else None
            cdims = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
            k = 1.0
            if lhs is not None and lhs in shapes and cdims:
                lm = _SHAPE_RE.search(shapes[lhs])
                if lm:
                    dims = [int(x) for x in lm.group(2).split(",") if x]
                    for ci in cdims.group(1).split(","):
                        if ci and int(ci) < len(dims):
                            k *= dims[int(ci)]
            flops += 2.0 * _shape_elems(out_shape) * k * m
    return HloStats(flops=flops, bytes=bytes_total, dot_count=dot_count)


def roofline_terms(cost: dict, coll: CollectiveStats, *, chips: int,
                   model_flops: Optional[float] = None) -> dict:
    """Three-term roofline: compute, HBM, collective — per chip of the
    ``TARGET_KIND``."""
    chip = PEAKS[TARGET_KIND]
    flops = float(cost.get("flops", 0.0))
    mem_bytes = float(cost.get("bytes accessed", 0.0))
    t_compute = flops / chip.bf16_flops
    t_memory = mem_bytes / chip.hbm_bytes_s
    t_collective = coll.total_bytes / chip.ici_bytes_s
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_collective}
    dominant = max(terms, key=terms.get)
    total = max(t_compute + t_memory + t_collective, 1e-30)
    out = {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "roofline_fraction": terms[dominant] / total,
    }
    if model_flops:
        out["useful_flops_ratio"] = float(model_flops) / max(flops * chips, 1e-30)
    return out
