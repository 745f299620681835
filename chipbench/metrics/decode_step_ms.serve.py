"""Device time of the jitted decode step per call (ms), from the trace."""
from chipbench.readers import DECODE, device_summary, mean_ms


def read(rec):
    s = device_summary(rec)
    return None if s is None else mean_ms(s.module_runs(DECODE))
