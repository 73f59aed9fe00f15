"""Operations and bytes the AFMoE decoder's algorithms require, from the
configuration's shapes alone (beside ``flops.py``, by the same rules: 2 FLOPs
per multiply-add, only the score pairs that are live, nothing recomputed,
routing as expected under a uniform router).  ``num_experts`` counts the
experts HELD here, ``router_width`` all that are scored.
"""


def attention_pairs(seq, window=0):
    """Live (query, key) pairs of one head over ``seq`` positions: causal,
    and inside the window where there is one (query i sees keys
    i - window < j <= i)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_pairs(cfg, seq):
    """[pairs] by layer, by the kind ``layer_types`` gives it."""
    return [attention_pairs(seq, cfg["sliding_window"]
                            if kind == "sliding_attention" else 0)
            for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def routed_rows(cfg, tokens):
    """Rows the held experts are expected to own a layer and step:
    tokens x picks a token x held / scored."""
    return tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg.get("router_width", cfg["num_experts"])


def matrix_macs_per_token(cfg):
    """Multiply-adds of one token through every matrix of the share: the
    attention block's five projections, a dense layer's gated FFN or an
    expert layer's router, shared expert and expected routed picks, and the
    vocabulary head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    gd = cfg["num_key_value_heads"] * cfg["head_dim"]
    attention = 3 * d * hd + 2 * d * gd             # Wq, Wz, Wo; Wk, Wv
    dense = 3 * d * cfg["intermediate_size"]
    one_expert = 3 * d * cfg["moe_intermediate_size"]
    expert = d * cfg.get("router_width", cfg["num_experts"]) \
        + one_expert * (cfg.get("num_shared_experts", 1)
                        + routed_rows(cfg, 1))
    n_dense = cfg["num_dense_layers"]
    n_expert = cfg["num_hidden_layers"] - n_dense
    return (cfg["num_hidden_layers"] * attention + n_dense * dense
            + n_expert * expert + d * v)


def train_flops_per_step(cfg, batch, seq):
    """Forward + backward (twice the forward) of ``batch`` sequences."""
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    forward = 2 * seq * matrix_macs_per_token(cfg) \
        + 4 * sum(layer_pairs(cfg, seq)) * hd
    return 3 * batch * forward


def flash_train_flops_bytes(cfg, batch, seq, itemsize=2):
    """Attention alone over one training step, all layers: the forward's
    two matrix products and the backward's four over the live pairs; bytes
    are one read of q, k, v (forward) and q, k, v, o, do (backward) and one
    write of o, dq, dk, dv, with K and V (and dK, dV) at the key/value
    heads' width, not repeated per query head."""
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    gd = cfg["num_key_value_heads"] * cfg["head_dim"]
    flops = batch * 6 * 2 * sum(layer_pairs(cfg, seq)) * hd
    per_token = 6 * hd + 6 * gd     # q o | q o do dq ;  k v | k v dk dv
    bytes_ = cfg["num_hidden_layers"] * batch * seq * per_token * itemsize
    return flops, bytes_


def moe_train_flops_bytes(cfg, batch, seq, itemsize=2):
    """The held experts' grouped products over one training step, all
    expert layers: three forward (W1, W3, W2), and for each its two
    backward products (by the rows, by the weights), over the expected
    routed rows; bytes are one read of both operands and one write of the
    result of each of the nine."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = routed_rows(cfg, batch * seq)
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    flops = layers * 9 * 2 * rows * d * f
    bytes_ = layers * 9 * (rows * (d + f) + cfg["num_experts"] * d * f) \
        * itemsize
    return flops, bytes_
