"""The longest pause of Python's garbage collector, on any thread, over
the runtime's whole life (``ServingRuntime.counters``, a ``gc.callbacks``
hook on the host clock)."""


def read(run):
    return run["counters"].get("gc_max_ms")
