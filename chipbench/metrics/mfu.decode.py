"""Model FLOPs of a decode step over its device time times the chip's
bf16 peak (%): the whole step's share of the peak, beside its HBM
roofline share."""
from chipbench.counts import decode_step
from chipbench.readers import DECODE, decode_context, device_summary


def read(rec):
    s = device_summary(rec)
    runs = [] if s is None else s.module_runs(DECODE)
    if not runs:
        return None
    t = rec.traffic
    work = decode_step.flops(rec.model, t["batch"], decode_context(t))
    return 100.0 * work * len(runs) / (sum(runs)
                                       * rec.peaks["bf16_flops_per_s"])
