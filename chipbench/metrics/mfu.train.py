"""Model FLOPs of the train steps the traced window ran, over the
window's length times the chip's bf16 peak (%): 6 * N * tokens plus
causal attention, the embedding lookup excluded (counts/train_step.py)."""
from chipbench.counts import train_step
from chipbench.readers import TRAIN_STEP, device_summary


def read(rec):
    s = device_summary(rec)
    if s is None or not s.module_runs(TRAIN_STEP):
        return None
    t = rec.traffic
    work = len(s.module_runs(TRAIN_STEP)) * train_step.flops(
        rec.model, t["global_batch"], t["seq_len"])
    return 100.0 * work / (s.window_s * s.n_chips
                           * rec.peaks["bf16_flops_per_s"])
