"""Host time of the data pipeline per step (ms): the benchmark's span
around ``pipeline.next_batch`` in the traced window."""
from chipbench.readers import mean_ms


def read(rec):
    if rec.summary is None:
        return None
    return mean_ms(rec.summary.spans.get("chipbench.next_batch", []))
