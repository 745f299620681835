"""Share of its roofline (%) the grouped-matmul kernel reached in the
decode steps: over its calls there (the ``tpu_custom_call``s of at most
one row tile whose operand is the experts' stacked weights), the least
time (FLOPs of the batch's routes over the bf16 peak, or the bytes of the
touched experts' weights and of the rows in and out over the HBM
bandwidth, whichever is longer) over the device time they took.  The
touched experts are the program's ``repro.serve.moe`` counter; a program
without it, or a trace without the kernel, reads none."""
from chipbench.counts import moe
from chipbench.readers import device_summary
from chipbench.readers_moe import experts_touched, gmm_calls


def read(rec):
    s = device_summary(rec)
    touched = experts_touched(rec)
    if s is None or touched is None:
        return None
    t, pk = rec.traffic, rec.peaks
    routes = t["batch"] * rec.model["num_experts_per_tok"]
    calls = gmm_calls(s, rec.model, t["batch"] * t["prompt_len"])
    secs = sum(c[3] for c in calls)
    if not secs:
        return None
    least = sum(runs * max(moe.gmm_flops(routes, k, n)
                           / pk["bf16_flops_per_s"],
                           moe.gmm_bytes(routes, k, n, touched)
                           / pk["hbm_bytes_per_s"])
                for _, k, n, _, runs in calls)
    return 100.0 * least / secs
