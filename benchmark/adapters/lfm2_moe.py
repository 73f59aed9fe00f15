"""How the program under test is built for an LFM2-MoE configuration
(``models/lfm2_moe.py`` through ``serving/decode.py``): the first stage of
a pipeline, serving only.

The program's entry points are imported as this file is loaded, which is when
the cell's serving harness is set up: a program that has none of them fails
there, at once, and not after the weights have been made."""
from mxnet_tpu.models import lfm2_moe
from mxnet_tpu.serving.decode import DecodeConfig, HybridDecodeProgram


def decode_program(cfg, traffic, weights):
    """``weights``: name -> host array in ``refs/lfm2_moe.param_shapes``'
    names (the program's own), values representable in the serving dtype."""
    dc = DecodeConfig(cfg["vocab_size"], cfg["num_hidden_layers"],
                      cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["n_positions"], page_size=traffic["page_size"],
                      max_seqs=traffic["slots"], family="lfm2_moe",
                      dtype=cfg["serving"]["dtype"],
                      prefill_tokens_per_step=traffic[
                          "prefill_tokens_per_step"],
                      model=lfm2_moe.model_of(cfg),
                      kv_heads=cfg["num_key_value_heads"])
    return HybridDecodeProgram(weights, dc, name="bench")


def serve_work(cfg, delta):
    """Required work of the engine steps counted in ``delta`` (the engine's
    own ``stats()`` counts).  Every prompt token taken in goes through the
    stack; so does every generated token but a request's last, which this
    counts too; only a generated token needs the vocabulary head; an expert
    layer costs a token its picks.  Attention is counted over
    ``attended``, the contexts of the SLOTS: a chunk's rows attend more than
    their slot's one context, so this is the least the steps can have done,
    not all of it (the traced run's ``gqa_attn_roofline`` has the pairs row
    by row)."""
    from benchmark.lib import lfm2_counts as counts
    tokens = delta["prefilled"] + delta["decoded"]
    flops = (tokens * counts.stack_flops_per_token(cfg)
             + delta["decoded"] * counts.head_flops_per_token(cfg))
    if delta.get("attended") is not None:
        flops += delta["attended"] * counts.pair_flops(cfg)
    return {"tokens": tokens, "flops": flops}
