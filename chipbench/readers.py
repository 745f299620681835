"""What the per-layer metrics' readers share: the program's module names,
the flash kernel's signature in the trace, and the roofline arithmetic.

A reader returns ``None`` where the trace holds nothing for it to read,
and never 0 for a share of a peak."""
from __future__ import annotations

import re

from chipbench.counts import flash_fwd

# the jitted step functions' names, as the device trace shows them
TRAIN_STEP = "jit_train_step"
PREFILL = "jit_prefill_step"
DECODE = "jit_serve_step"


def device_summary(rec):
    """The trace summary, or None where no device was traced."""
    s = rec.summary
    return s if s is not None and s.n_chips > 0 else None


def mean_ms(values) -> float | None:
    return 1e3 * sum(values) / len(values) if values else None


def flash_time(summary, batch: int, heads: int, seq: int, head_dim: int):
    """(seconds, calls) of the flash-forward kernel at this shape: a
    ``tpu_custom_call`` whose results are o (B, H, S, D) in bf16 and the
    log-sum-exp (B, H, S, 1) in f32."""
    out = re.compile(rf"= \(bf16\[{batch},{heads},{seq},{head_dim}\]\S*, "
                     rf"f32\[{batch},{heads},{seq},1\]")
    secs = calls = 0
    for text, (t, n) in summary.ops.items():
        if 'custom_call_target="tpu_custom_call"' in text and out.search(text):
            secs += t
            calls += n
    return secs, calls


def flash_roofline(rec, batch: int, seq: int) -> float | None:
    """Share (%) of its roofline the flash forward reached: the least time
    of its calls (FLOPs over the bf16 peak or bytes over the HBM
    bandwidth, whichever is longer) over the time they took."""
    s = device_summary(rec)
    if s is None:
        return None
    m, pk = rec.model, rec.peaks
    H, KV, D = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    secs, calls = flash_time(s, batch, H, seq, D)
    if not calls:
        return None
    least = max(flash_fwd.flops(batch, seq, H, D) / pk["bf16_flops_per_s"],
                flash_fwd.bytes_moved(batch, seq, H, KV, D)
                / pk["hbm_bytes_per_s"])
    return 100.0 * least * calls / secs


def decode_context(traffic: dict) -> float:
    """Mean number of cached positions a decode call attends to: call i
    of a batch (0-based) sees the prompt and i + 1 new positions."""
    return traffic["prompt_len"] + (traffic["new_tokens"] + 1) / 2
