"""The longest pause of Python's garbage collector, on any thread, over
the runtime's whole life (``ServingRuntime.counters``, a ``gc.callbacks``
hook on the host clock).  The deep1m-inf.steady cell's share of the
quantity: its ``p99_ms`` is read per layer, so this one moves its
``p50_ms``."""


def read(run):
    return run["counters"].get("gc_max_ms")
