"""Device ms per dispatched batch of the exact scan, from the device trace:
the brute engine's whole call (``core/scan.topk_scan`` with the corpus copy
and pad in front of it), XLA module ``jit_brute_force`` on a v5e."""
from chipbench.layers import device_ms_per_batch

MODULES = (r"^jit_brute_force$",)


def read(run):
    return device_ms_per_batch(run, MODULES)
