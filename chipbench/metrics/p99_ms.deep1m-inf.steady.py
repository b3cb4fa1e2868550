"""The client's 99th-percentile latency of the traced run, in ms, reckoned
as the end-to-end ``p99_ms`` is (every request due in the window, a miss
ranked above every answer).  In ``deep1m-inf.steady`` the device is ~97%
busy, so a host stall of a second builds a queue that outlives it and
this tail swings from run to run; it is read here, per layer, and not
held to a bound."""


def read(run):
    return run["e2e"]["p99_ms"]
