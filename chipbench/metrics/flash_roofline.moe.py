"""Share of its roofline (%) the flash-forward kernel reached in the
sparse-expert prefill.  A prefill calls it once a layer, every call at
the same shape: a sliding layer's call needs only the (query, key) pairs
inside its window (``counts/moe.py:window_pairs``), a full layer's the
causal ones, and each reads q, k, v and writes o and the log-sum-exp
once.  The least time of the calls (FLOPs over the bf16 peak or bytes
over the HBM bandwidth, whichever is longer, a layer's mean) over the
time they took."""
from chipbench.counts import flash_fwd, moe
from chipbench.readers import device_summary, flash_time


def read(rec):
    s = device_summary(rec)
    if s is None:
        return None
    t, m, pk = rec.traffic, rec.model, rec.peaks
    B, S = t["batch"], t["prompt_len"]
    H, KV, D = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    secs, calls = flash_time(s, B, H, S, D)
    if not calls:
        return None
    windows = moe.layer_windows(m)
    bound = flash_fwd.bytes_moved(B, S, H, KV, D) / pk["hbm_bytes_per_s"]
    least = sum(max(moe.flash_flops(B, S, H, D, w) / pk["bf16_flops_per_s"],
                    bound) for w in windows) / len(windows)
    return 100.0 * least * calls / secs
