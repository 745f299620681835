"""Device time of the jitted train step per step (ms), from the trace."""
from chipbench.readers import TRAIN_STEP, device_summary, mean_ms


def read(rec):
    s = device_summary(rec)
    return None if s is None else mean_ms(s.module_runs(TRAIN_STEP))
