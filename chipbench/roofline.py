"""Peaks of the chip and the least time of a kernel's work.

``PEAKS`` is copied from the program's ``dist/roofline.PEAKS`` with its
source, and keyed the same way, by the ``device_kind`` JAX reports.  A kind
that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_s": 819e9,
        "ici_bytes_s": 1600e9 / 8,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


def scan_cost(batch: int, n: int, d: int, k: int) -> tuple:
    """(operations, bytes) an exact top-k scan of ``batch`` float32
    queries over ``n`` float32 rows of width ``d`` needs: one multiply-add
    per query, row and dimension; the corpus and the queries read once, the
    (id, distance) answers written once."""
    flops = 2.0 * batch * n * d
    nbytes = n * d * 4.0 + batch * d * 4.0 + batch * k * 8.0
    return flops, nbytes


def least_time(flops: float, nbytes: float, kind: str) -> tuple:
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak bandwidth, and which of the two it is."""
    p = peaks(kind)
    t_c = flops / p["bf16_flops"]
    t_m = nbytes / p["hbm_bytes_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
