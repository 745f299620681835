"""Share of the traced training window (%) in which no op ran on the
device."""
from chipbench.readers import device_summary


def read(rec):
    s = device_summary(rec)
    return None if s is None else 100.0 * s.idle_share
