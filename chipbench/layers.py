"""Shared arithmetic of the per-layer metric readers."""
from __future__ import annotations

from chipbench import tracing


def device_ms_per_batch(run: dict, patterns):
    """Device ms of the matching modules inside the traced window, per
    batch the server dispatched in it and per chip (both clipped to the
    window's ``chipbench.trace`` span): the modules' seconds, which
    ``tracing.reduce`` sums over the device planes, over the devices that
    ran in the window, so a batch on four chips reads its per-chip time
    and one on one chip what it reads without the division; None where
    nothing was traced."""
    secs, count = tracing.module_seconds(run["trace"], patterns)
    batches = run["trace"]["batches"]
    devices = run["trace"]["devices"]
    if count == 0 or batches <= 0 or devices <= 0:
        return None
    return 1e3 * secs / devices / batches
