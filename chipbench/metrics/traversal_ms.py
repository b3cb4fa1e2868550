"""Device ms per dispatched batch under the infinity engine's
``traversal`` stage (the VP-tree program, best-first or beam), from the
device trace: the union of the stage's operation intervals inside the
traced window (``chipbench/spans.py``), so operations nested in the
best-first ``while`` count once."""
from chipbench import spans


def read(run):
    red = spans.of_run(run)
    if red is None or "traversal" not in red["stage_s"]:
        return None
    return spans.per_batch_ms(run, red["stage_s"]["traversal"])
