"""Model FLOPs of the prefills and decode steps the traced window ran,
over the window's length times the chip's bf16 peak (%)."""
from chipbench.counts import decode_step, prefill
from chipbench.readers import DECODE, PREFILL, decode_context, device_summary


def read(rec):
    s = device_summary(rec)
    if s is None:
        return None
    t, m = rec.traffic, rec.model
    n_pre, n_dec = len(s.module_runs(PREFILL)), len(s.module_runs(DECODE))
    if not n_pre + n_dec:
        return None
    work = (n_pre * prefill.flops(m, t["batch"], t["prompt_len"])
            + n_dec * decode_step.flops(m, t["batch"], decode_context(t)))
    return 100.0 * work / (s.window_s * s.n_chips
                           * rec.peaks["bf16_flops_per_s"])
