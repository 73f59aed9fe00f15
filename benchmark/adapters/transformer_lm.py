"""How the program under test is built for a decoder-only language model
configuration: the only place (with ``resnet_v2.py``) where the benchmark
names the program's model entry points."""
import numpy as np


def train_symbol(cfg):
    from mxnet_tpu.models import transformer
    return transformer.get_symbol(
        vocab_size=cfg["vocab_size"], seq_len=cfg["n_positions"],
        num_layers=cfg["n_layer"], hidden=cfg["n_embd"], heads=cfg["n_head"])


def train_shapes(cfg, traffic):
    shape = (traffic["batch"], cfg["n_positions"])
    return {"data": shape, "softmax_label": shape}, {}


def train_batches(cfg, traffic, seed):
    """A rotating set of host batches: random token rows that all differ,
    labels the next token.  Ids travel as float32, the type the program's
    training graph takes (exact up to 2**24)."""
    rs = np.random.default_rng([int(seed), 1])
    b, t = traffic["batch"], cfg["n_positions"]
    out = []
    for _ in range(traffic["rotating_batches"]):
        rows = rs.integers(0, cfg["vocab_size"], (b, t + 1))
        out.append({"data": rows[:, :-1].astype(np.float32),
                    "softmax_label": rows[:, 1:].astype(np.float32)})
    return out


def work_per_step(cfg, traffic):
    from benchmark.lib import flops
    b, t = traffic["batch"], cfg["n_positions"]
    f, by = flops.flash_train_flops_bytes(cfg, b, t)
    return {"flops": flops.lm_train_flops_per_step(cfg, b, t),
            "items": b * t, "flash_flops": f, "flash_bytes": by}


def decode_program(cfg, traffic, weights):
    """``weights``: name -> host float32 array, in the training names."""
    from mxnet_tpu.serving.decode import DecodeConfig, DecodeProgram
    dc = DecodeConfig(cfg["vocab_size"], cfg["n_layer"], cfg["n_embd"],
                      cfg["n_head"], cfg["n_positions"],
                      page_size=traffic["page_size"],
                      max_seqs=traffic["slots"])
    return DecodeProgram(weights, dc, name="bench")


def serve_work(cfg, delta):
    """Required work of the engine steps counted in ``delta`` (the engine's
    own ``stats()`` counts): every token processed (prefill or decode) goes
    through the stack, only a decoded token needs the vocabulary head, and
    attention reads the contexts that were attended, where the driver's
    wrapper could see them (``attended`` is None where it could not: the
    attention term, some 6 % at these lengths, is then left out and the
    kernel's roofline is not reported)."""
    from benchmark.lib import flops
    tokens = delta["prefilled"] + delta["decoded"]
    f = (tokens * flops.lm_forward_flops_per_token(cfg, head=False)
         + delta["decoded"] * 2 * cfg["n_embd"] * cfg["vocab_size"])
    work = {"tokens": tokens}
    if delta.get("attended") is not None:
        f += flops.lm_attention_forward_flops(cfg, delta["attended"])
        work["decode_attn_bytes"] = flops.decode_attention_bytes(
            cfg, delta["attended"])
    work["flops"] = f
    return work
