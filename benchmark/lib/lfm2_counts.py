"""Operations and bytes of the LFM2-MoE serving step, from the
configuration's shapes and the program's own counts (the least work: nothing
computed twice is counted twice, padding is not counted)."""


def _kinds(cfg):
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attention_layers(cfg):
    return _kinds(cfg).count("full_attention")


def flops_per_token_by_part(cfg):
    """{part: FLOPs a token a LAYER that has the part}: the convolution's
    input projection, its taps and gates (``shift``: B X, the L taps, C z)
    and output projection; attention's q, k, v and output projections; the
    dense layer's gated FFN; the router; ONE routed expert (times the
    picks)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Hkv, hd = cfg["num_key_value_heads"], head_dim(cfg)
    return {"conv_in": 2 * d * 3 * d,
            "shift": 2 * d * cfg["conv_L_cache"] + 2 * d,
            "conv_out": 2 * d * d,
            "qkv": 2 * d * (H + 2 * Hkv) * hd,
            "o": 2 * H * hd * d,
            "dense_ffn": 6 * d * cfg["intermediate_size"],
            "router": 2 * d * cfg["num_experts"],
            "routed": 6 * d * cfg["moe_intermediate_size"]}


def stack_flops_per_token(cfg):
    """FLOPs a token through every layer, attention's pairs and the head
    left out; an expert layer at its picks (every expert is held)."""
    part = flops_per_token_by_part(cfg)
    total = 0
    for i, kind in enumerate(_kinds(cfg)):
        if kind == "conv":
            total += part["conv_in"] + part["shift"] + part["conv_out"]
        else:
            total += part["qkv"] + part["o"]
        if i < cfg["num_dense_layers"]:
            total += part["dense_ffn"]
        else:
            total += part["router"] + cfg["num_experts_per_tok"] \
                * part["routed"]
    return total


def head_flops_per_token(cfg):
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def pair_flops(cfg):
    """FLOPs of one (query row, cached position) pair in every attention
    layer: every query head's score and weighted sum, hd lanes each."""
    return attention_layers(cfg) * 4 * cfg["num_attention_heads"] \
        * head_dim(cfg)


def kv_bytes_per_position(cfg, itemsize=2):
    """Bytes of one position's keys and values in every attention layer."""
    return attention_layers(cfg) * 2 * cfg["num_key_value_heads"] \
        * head_dim(cfg) * itemsize


def gqa_attn_least(cfg, steps, itemsize=2):
    """(FLOPs, bytes) the attention of ``steps`` needs at least, all layers.
    A step is ``(attended, attn_pairs)``, the ``serve/decode_step`` span's
    counts: each slot's context read once, every pair's products once."""
    attended = sum(a for a, _p in steps)
    pairs = sum(p for _a, p in steps)
    return pairs * pair_flops(cfg), \
        attended * kv_bytes_per_position(cfg, itemsize)


def conv_state_bytes(cfg, slots, itemsize=2):
    """Bytes of every slot's convolution state: its last L inputs a layer."""
    return _kinds(cfg).count("conv") * slots * cfg["conv_L_cache"] \
        * cfg["hidden_size"] * itemsize
