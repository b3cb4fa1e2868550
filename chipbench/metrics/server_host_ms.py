"""Host ms per dispatched batch that ``SearchServer.query`` spends around
the engine: its ``pad`` span (numpy pad, copy to the device) and its
``fetch`` span (copies of the answer back, the result built), from the
program's spans in the traced window (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    red = spans.of_run(run)
    if red is None or not {"pad", "fetch"} & set(red["host_s"]):
        return None
    host = red["host_s"]
    return spans.per_batch_ms(run,
                              host.get("pad", 0.0) + host.get("fetch", 0.0))
