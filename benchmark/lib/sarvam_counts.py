"""Operations and bytes of the Sarvam-MLA serving step, from the
configuration's shapes and the program's own counts (the least work: nothing
computed twice is counted twice, padding is not counted)."""

LAYER_PARTS = ("q", "kv_a", "absorb", "proj", "dense_ffn", "router", "shared",
               "routed")


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["kv_lora_rank"], cfg["v_head_dim"])


def row_width(cfg):
    """Elements of one cached row: the latent and the rope key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def flops_per_token_by_part(cfg):
    """{part: FLOPs a token a LAYER that has the part}: the query and
    latent projections, the two absorption products (``W_UK^T q_n`` and
    ``W_UV u``), the output projection, the dense layer's gated FFN, the
    router over all the experts scored, the shared expert, and ONE routed
    expert (times the held picks)."""
    d, H, nope, rope, lat, v = _dims(cfg)
    f = cfg["moe_intermediate_size"]
    return {"q": 2 * d * H * (nope + rope),
            "kv_a": 2 * d * (lat + rope),
            "absorb": 2 * H * lat * (nope + v),
            "proj": 2 * H * v * d,
            "dense_ffn": 6 * d * cfg["intermediate_size"],
            "router": 2 * d * cfg.get("router_width", cfg["num_experts"]),
            "shared": 6 * d * f * cfg["num_shared_experts"],
            "routed": 6 * d * f}


def stack_flops_per_token(cfg):
    """FLOPs a token through every layer, attention's pairs and the head
    left out; an expert layer at its EXPECTED held picks (picks a token
    times held over scored)."""
    part = flops_per_token_by_part(cfg)
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], layers)
    held_picks = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                  / cfg.get("router_width", cfg["num_experts"]))
    attention = part["q"] + part["kv_a"] + part["absorb"] + part["proj"]
    return (layers * attention + dense * part["dense_ffn"]
            + (layers - dense) * (part["router"] + part["shared"]
                                  + held_picks * part["routed"]))


def head_flops_per_token(cfg):
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def expanded_pair_flops(cfg):
    """FLOPs of one (query row, cached position) pair in every layer, in the
    expanded form: H heads of a (nope + rope)-wide score and a v-wide sum."""
    _d, H, nope, rope, _lat, v = _dims(cfg)
    return cfg["num_hidden_layers"] * 2 * H * (nope + rope + v)


def absorbed_pair_flops(cfg):
    """... in the absorbed form ``mla_attn`` computes, ONE layer: H heads
    of a (latent + rope)-wide score and a latent-wide sum."""
    _d, H, _nope, rope, lat, _v = _dims(cfg)
    return 2 * H * (2 * lat + rope)


def upproject_flops(cfg):
    """FLOPs that turn ONE cached latent row into every head's key and
    value (``W_kv_b``), ONE layer: what the expanded form pays a position
    each time a chunk attends it."""
    _d, H, nope, _rope, lat, v = _dims(cfg)
    return 2 * lat * H * (nope + v)


def latent_bytes(cfg, attended, itemsize=2):
    """Bytes of the latent rows of ``attended`` cached positions, ONE layer
    (each context read once, at the row's own 576 elements)."""
    return attended * row_width(cfg) * itemsize


def mla_attn_least(cfg, steps):
    """(FLOPs, bytes) the attention of ``steps`` needs at least, all layers.
    A step is ``(attended, attn_pairs, chunk_pairs, chunk_attended)``, the
    ``serve/decode_step`` span's counts: the contexts' latent rows are read
    once; a decoding row's pairs cost the absorbed form's products (one row
    cannot pay for an up-projection); a step's chunk costs the cheaper of
    the absorbed form over its pairs and the expanded form over them plus
    the up-projection of every position its slots hold, whichever form the
    kernel runs.  (Where a chunk spans two slots the choice is made for both
    at once: a prompt's short tail and the next one's head, a few percent of
    a chunk at most.)"""
    layers = cfg["num_hidden_layers"]
    absorbed = absorbed_pair_flops(cfg)
    expanded = expanded_pair_flops(cfg) // layers
    flops = bytes_ = 0
    for attended, pairs, chunk_pairs, chunk_attended in steps:
        flops += (pairs - chunk_pairs) * absorbed + min(
            chunk_pairs * absorbed,
            chunk_pairs * expanded + chunk_attended * upproject_flops(cfg))
        bytes_ += latent_bytes(cfg, attended)
    return layers * flops, layers * bytes_


def held_experts_least(cfg, expert_rows, experts_touched, itemsize=2):
    """(FLOPs, bytes) of the held experts' three grouped products at
    ``expert_rows`` held picks (summed over the expert layers) with
    ``experts_touched`` experts' matrices read (likewise summed)."""
    per_expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (2 * per_expert * expert_rows,
            per_expert * itemsize * experts_touched)
