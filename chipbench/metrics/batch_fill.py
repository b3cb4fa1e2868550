"""Requests answered per batch the runtime formed over the window
(``ServingRuntime.counters``: completed / batches)."""


def read(run):
    c = run["counters"]
    return c["completed"] / c["batches"] if c.get("batches") else None
