"""Shared arithmetic of the per-layer metric readers."""
from __future__ import annotations

from chipbench import tracing


def device_ms_per_batch(run: dict, patterns):
    """Device ms of the matching modules inside the traced window, per
    batch the server dispatched in it (both clipped to the window's
    ``chipbench.trace`` span); None where nothing was traced."""
    secs, count = tracing.module_seconds(run["trace"], patterns)
    batches = run["trace"]["batches"]
    if count == 0 or batches <= 0:
        return None
    return 1e3 * secs / batches
