"""K and V bytes of the contexts actually attended in the traced steps (sum
of the slots' sequence lengths, float32, all layers) over the HBM peak, over
the decode_attn kernel's device time: bytes bound it."""
from benchmark.lib import readers


def read(facts):
    traced = facts.get("traced")
    if not traced or "decode_attn_bytes" not in traced.get("serve_work", ()):
        return None
    return readers.roofline_pct(facts, "decode_attn", 0.0,
                                traced["serve_work"]["decode_attn_bytes"])
