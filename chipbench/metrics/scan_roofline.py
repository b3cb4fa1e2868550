"""The exact scan's share of its roofline, %: the least time the chip
could take for the batches dispatched in the traced window, over the
scan's device time (XLA module ``jit_brute_force``, the brute engine's
whole call).  A batch's least time is the larger of operations over peak
FLOP/s and bytes over peak HBM bandwidth for its real queries
(``roofline.scan_cost``); each batch counts by the share of its
``chipbench.query`` span inside the window.  At 10M x 96 the memory bound
applies."""
from chipbench import roofline, tracing

MODULES = (r"^jit_brute_force$",)


def read(run):
    secs, count = tracing.module_seconds(run["trace"], MODULES)
    if count == 0 or not run["trace"]["queries"]:
        return None
    least = 0.0
    for share, b in run["trace"]["queries"]:
        flops, nbytes = roofline.scan_cost(b, run["n"], run["d"], run["k"])
        least += share * roofline.least_time(flops, nbytes,
                                             run["device_kind"])[0]
    return 100.0 * least / secs
