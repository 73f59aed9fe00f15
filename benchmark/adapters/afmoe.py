"""How the program under test is built for an AFMoE decoder configuration
(``models/afmoe.py``): one chip's share of its expert layers, training only."""
import numpy as np


def train_symbol(cfg):
    from mxnet_tpu.models import afmoe
    return afmoe.get_symbol(cfg)


def train_shapes(cfg, traffic):
    shape = (traffic["batch"], traffic["seq_len"])
    return {"data": shape, "softmax_label": shape}, {}


def train_batches(cfg, traffic, seed):
    """A rotating set of host batches: random token rows that all differ,
    ids drawn from the vocabulary's slice, labels the next token.  Ids
    travel as float32, the type the program's training graph takes (exact
    up to 2**24)."""
    rs = np.random.default_rng([int(seed), 1])
    b, t = traffic["batch"], traffic["seq_len"]
    out = []
    for _ in range(traffic["rotating_batches"]):
        rows = rs.integers(0, cfg["vocab_size"], (b, t + 1))
        out.append({"data": rows[:, :-1].astype(np.float32),
                    "softmax_label": rows[:, 1:].astype(np.float32)})
    return out


def work_per_step(cfg, traffic):
    from benchmark.lib import afmoe_counts as counts
    b, t = traffic["batch"], traffic["seq_len"]
    flash_flops, flash_bytes = counts.flash_train_flops_bytes(cfg, b, t)
    moe_flops, moe_bytes = counts.moe_train_flops_bytes(cfg, b, t)
    return {"flops": counts.train_flops_per_step(cfg, b, t), "items": b * t,
            "flash_flops": flash_flops, "flash_bytes": flash_bytes,
            "moe_flops": moe_flops, "moe_bytes": moe_bytes}
