"""The longest interval, over the runtime's whole life, from the end of
one ``server.query`` to the start of the next among those that begin with
requests in the queue (``ServingRuntime.counters``, host clock): the
longest time the device waited on the runtime rather than on traffic.
The deep1m-inf.steady cell's share of the quantity: its ``p99_ms`` is
read per layer, so this one moves its ``p50_ms``."""


def read(run):
    return run["counters"].get("dispatch_gap_max_ms")
