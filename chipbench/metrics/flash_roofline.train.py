"""Share of its roofline (%) the flash-forward kernel reached in the
train step (the forward and its rematerialization both call it)."""
from chipbench.readers import flash_roofline


def read(rec):
    t = rec.traffic
    return flash_roofline(rec, t["global_batch"], t["seq_len"])
