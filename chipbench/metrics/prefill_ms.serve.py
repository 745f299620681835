"""Device time of the jitted prefill per batch (ms), from the trace."""
from chipbench.readers import PREFILL, device_summary, mean_ms


def read(rec):
    s = device_summary(rec)
    return None if s is None else mean_ms(s.module_runs(PREFILL))
