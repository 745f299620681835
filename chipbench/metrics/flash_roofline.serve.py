"""Share of its roofline (%) the flash-forward kernel reached in the
prefill."""
from chipbench.readers import flash_roofline


def read(rec):
    t = rec.traffic
    return flash_roofline(rec, t["batch"], t["prompt_len"])
