"""Model FLOPs of the sparse-expert prefills and decode steps the traced
window ran, over the window's length times the chip's bf16 peak (%): the
active parameters (a token's experts, not all of them) and
window-limited attention (counts/moe.py).  The whole step's share."""
from chipbench.counts import moe
from chipbench.readers import DECODE, PREFILL, decode_context, device_summary


def read(rec):
    s = device_summary(rec)
    if s is None:
        return None
    t, m = rec.traffic, rec.model
    n_pre, n_dec = len(s.module_runs(PREFILL)), len(s.module_runs(DECODE))
    if not n_pre + n_dec:
        return None
    work = (n_pre * moe.prefill_flops(m, t["batch"], t["prompt_len"])
            + n_dec * moe.decode_flops(m, t["batch"], decode_context(t)))
    return 100.0 * work / (s.window_s * s.n_chips
                           * rec.peaks["bf16_flops_per_s"])
