"""How the program under test is built for a Sarvam-MLA configuration
(``models/sarvam_mla.py`` through ``serving/decode.py``): one chip's share of
its expert layers, serving only.

The program's entry points are imported as this file is loaded, which is when
the driver is made: a program that has none of them (every commit before
PR 35) fails there, at once, and not after the weights have been made."""
from mxnet_tpu.models import sarvam_mla
from mxnet_tpu.serving.decode import DecodeConfig, LatentDecodeProgram


def decode_program(cfg, traffic, weights):
    """``weights``: name -> host array in ``refs/sarvam_mla.param_shapes``'
    names (the program's own), values representable in the serving dtype."""
    dc = DecodeConfig(cfg["vocab_size"], cfg["num_hidden_layers"],
                      cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["n_positions"], page_size=traffic["page_size"],
                      max_seqs=traffic["slots"], family="sarvam_mla",
                      dtype=cfg["serving"]["dtype"],
                      prefill_tokens_per_step=traffic[
                          "prefill_tokens_per_step"],
                      model=sarvam_mla.model_of(cfg))
    return LatentDecodeProgram(weights, dc, name="bench")


def serve_work(cfg, delta):
    """Required work of the engine steps counted in ``delta`` (the engine's
    own ``stats()`` counts).  Every prompt token taken in goes through the
    stack; so does every generated token but a request's last, which this
    counts too (one row in some 3,500); only a generated token needs the
    vocabulary head; an expert layer costs a token its expected one held pick
    (8 picks, 16 of 128 experts here).  Attention is counted at the expanded
    form's products over ``attended``, the contexts of the SLOTS: a chunk's
    rows attend more than their slot's one context, so this is the least the
    steps can have done, not all of it (the traced run's ``mla_attn_roofline``
    has the pairs row by row)."""
    from benchmark.lib import sarvam_counts as counts
    tokens = delta["prefilled"] + delta["decoded"]
    flops = (tokens * counts.stack_flops_per_token(cfg)
             + delta["decoded"] * counts.head_flops_per_token(cfg))
    if delta.get("attended") is not None:
        flops += delta["attended"] * counts.expanded_pair_flops(cfg)
    return {"tokens": tokens, "flops": flops}
