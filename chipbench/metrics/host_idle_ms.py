"""Device-idle ms per dispatched batch while the runtime's batcher thread
was in a program span other than ``wait``: the chip waiting on host work
(padding, launches, copies, answers handed back, a collector pause), not
on traffic.  From the device trace and the program's spans in the traced
window (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    red = spans.of_run(run)
    if red is None or not red["idle_s"]:
        return None
    return spans.per_batch_ms(run, spans.host_idle_s(red))
