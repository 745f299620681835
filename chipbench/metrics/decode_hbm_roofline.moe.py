"""Share of the HBM roofline (%) the sparse-expert decode step reached:
the bytes an exact step must read and write (counts/moe.py: attention
weights, router, norms, the touched experts, lm head, the valid KV of the
full layers and the sliding layers' windows) over the HBM bandwidth, over
the decode module's mean device time.  The touched experts are the
program's ``repro.serve.moe`` counter; without it this reads none."""
from chipbench.counts import moe
from chipbench.readers import DECODE, decode_context, device_summary
from chipbench.readers_moe import experts_touched


def read(rec):
    s = device_summary(rec)
    runs = [] if s is None else s.module_runs(DECODE)
    touched = experts_touched(rec)
    if not runs or touched is None:
        return None
    t = rec.traffic
    least = moe.decode_bytes(rec.model, t["batch"], decode_context(t),
                             touched) / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least * len(runs) / sum(runs)
