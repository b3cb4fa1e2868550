"""Device ms per dispatched batch under the infinity engine's ``rerank``
stage (original-metric scores of the candidates and the top-k, run op by
op), from the device trace: the union of the intervals of the modules
launched inside the host ``rerank`` span, inside the traced window
(``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    red = spans.of_run(run)
    if red is None or "rerank" not in red["stage_s"]:
        return None
    return spans.per_batch_ms(run, red["stage_s"]["rerank"])
