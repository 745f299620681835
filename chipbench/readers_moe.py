"""What the sparse-expert readers share: the routing counter the program
records, and the grouped-matmul kernel's calls in the device trace.

A reader returns ``None`` where the trace or the program holds nothing
for it to read, and never 0 for a share of a peak."""
from __future__ import annotations

import re

GMM_OUT = re.compile(r"%?\S+ = bf16\[(\d+),(\d+)\]")


def experts_touched(rec) -> float | None:
    """Mean distinct experts a layer's decode step read: the
    ``experts_touched`` id of the program's ``repro.serve.moe`` spans in
    the traced window (one a batch)."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    seen = [r.ids["experts_touched"] for r in spans.captured()
            if r.name == "repro.serve.moe" and r.ids.get("decode_steps")]
    return sum(seen) / len(seen) if seen else None


def gmm_calls(summary, m: dict, max_rows: int) -> list:
    """[(rows, k, n, seconds, runs)] of the grouped-matmul kernel calls
    with fewer than ``max_rows`` rows: a ``tpu_custom_call`` whose result
    is bf16 (rows, n) and one of whose operands is experts' stacked
    weights, bf16 (groups, k, n) with groups a multiple of the experts (a
    layer's, or every layer's of a scanned group)."""
    weights = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")
    calls = []
    for text, (secs, runs) in summary.ops.items():
        if 'custom_call_target="tpu_custom_call"' not in text:
            continue
        out = GMM_OUT.match(text)
        if out is None:
            continue
        rows, n = int(out[1]), int(out[2])
        kn = {(int(k), int(wn)) for g, k, wn in weights.findall(text)
              if int(g) % m["num_experts"] == 0 and int(wn) == n}
        if len(kn) == 1 and rows < max_rows:
            calls.append((rows, *kn.pop(), secs, runs))
    return calls
