"""Share of the HBM roofline (%) the decode step reached: the bytes an
exact step must read and write (counts/decode_step.py: weights, lm head,
the valid KV cache) over the HBM bandwidth, over the decode module's
mean device time.  Bytes, not FLOPs, bound this step."""
from chipbench.counts import decode_step
from chipbench.readers import DECODE, decode_context, device_summary


def read(rec):
    s = device_summary(rec)
    runs = [] if s is None else s.module_runs(DECODE)
    if not runs:
        return None
    t = rec.traffic
    least = decode_step.bytes_moved(rec.model, t["batch"], decode_context(t)) \
        / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least * len(runs) / sum(runs)
