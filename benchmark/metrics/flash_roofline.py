"""flash_fwd + flash_bwd_dq + flash_bwd_dkv: least time from the shapes over
their summed device time in the traced steps (compute bounds it at T=1024:
0.39 ms of FLOPs against 0.37 ms of bytes a layer at batch 16)."""
from benchmark.lib import readers


def read(facts):
    traced = facts.get("traced")
    work = facts["work_per_step"]
    if not traced or "flash_flops" not in work:
        return None
    n = traced["steps"]
    return readers.roofline_pct(facts, "flash_", n * work["flash_flops"],
                                n * work["flash_bytes"])
