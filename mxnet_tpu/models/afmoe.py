"""AFMoE decoder language model (Arcee Trinity family, ``model_type:
afmoe``): RMSNorm round both residual branches, grouped-query attention
with per-head q/k norms and a sigmoid output gate, sliding-window layers
(rotary positions) mixed with full-attention layers (none), leading dense
gated FFNs and then sigmoid top-k routed experts with one shared expert.

Layout: tokens (N, T) -> Embedding * sqrt(d) -> L x [attention, FFN or
experts] -> RMSNorm -> vocab head -> per-token SoftmaxOutput.  Built from
the stack's own operators: ``RMSNorm``, ``rotary_embedding``,
``fused_attention`` (window, fewer key/value heads), ``moe_ffn``.

The expert layers are ONE chip's share under expert parallelism: each
holds ``experts_held`` of the ``num_experts`` the router scores, from
``first_expert`` on (``ops/nn.py::_contrib_moe_ffn``); with
``experts_held == num_experts`` the layer is whole.  Only training is
built here: serving this model needs a page pool with two kinds of layer
(window and full) that ``serving/decode.py`` does not have; the many-token
step and experts inside it came with ``models/sarvam_mla.py``.
"""
from .. import symbol as sym


def _gated_ffn(x, hidden, width, p):
    up = sym.FullyConnected(x, num_hidden=width, flatten=False, no_bias=True,
                            name=p + "ff1")
    lin = sym.FullyConnected(x, num_hidden=width, flatten=False, no_bias=True,
                             name=p + "ff3")
    return sym.FullyConnected(
        sym.Activation(up, act_type="silu", name=p + "act") * lin,
        num_hidden=hidden, flatten=False, no_bias=True, name=p + "ff2")


def _attention(x, cfg, sliding, p, flash_min_seq):
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv_heads, dim = cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]

    def project(name, n_heads):
        y = sym.FullyConnected(x, num_hidden=n_heads * dim, flatten=False,
                               no_bias=True, name=p + name)
        return sym.Reshape(y, shape=(0, 0, n_heads, dim))

    q = sym.RMSNorm(project("q", heads), eps=eps, name=p + "qn")
    k = sym.RMSNorm(project("k", kv_heads), eps=eps, name=p + "kn")
    v = project("v", kv_heads)
    gate = sym.FullyConnected(x, num_hidden=heads * dim, flatten=False,
                              no_bias=True, name=p + "gate")
    if sliding:     # full-attention layers carry no positions
        q = sym.contrib.rotary_embedding(q, theta=cfg["rope_theta"],
                                         name=p + "q_rope")
        k = sym.contrib.rotary_embedding(k, theta=cfg["rope_theta"],
                                         name=p + "k_rope")
    att = sym.contrib.fused_attention(
        q, k, v, causal=True,
        window=cfg["sliding_window"] if sliding else 0,
        flash_min_seq=flash_min_seq, name=p + "attn")
    att = sym.Reshape(att, shape=(0, 0, -1)) \
        * sym.Activation(gate, act_type="sigmoid", name=p + "gate_act")
    return sym.FullyConnected(att, num_hidden=hidden, flatten=False,
                              no_bias=True, name=p + "proj")


def _layer(x, cfg, idx, flash_min_seq):
    p = "l%d_" % idx
    eps = cfg["rms_norm_eps"]
    hidden = cfg["hidden_size"]
    sliding = cfg["layer_types"][idx] == "sliding_attention"
    a = _attention(sym.RMSNorm(x, eps=eps, name=p + "ln1"), cfg, sliding, p,
                   flash_min_seq)
    x = x + sym.RMSNorm(a, eps=eps, name=p + "ln2")
    m = sym.RMSNorm(x, eps=eps, name=p + "ln3")
    if idx < cfg["num_dense_layers"]:
        u = _gated_ffn(m, hidden, cfg["intermediate_size"], p)
    else:
        u = sym.contrib.moe_ffn(
            m, num_experts=cfg["router_width"],
            experts_held=cfg["num_experts"],
            first_expert=cfg.get("first_expert", 0),
            top_k=cfg["num_experts_per_tok"],
            num_hidden=cfg["moe_intermediate_size"],
            route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
            bias_update_rate=cfg["load_balance_coeff"], name=p + "moe")
    return x + sym.RMSNorm(u, eps=eps, name=p + "ln4")


def get_symbol(cfg, flash_min_seq=0, **kwargs):
    """A SoftmaxOutput-headed AFMoE symbol from the keys of the model's
    ``config.json`` (``hidden_size``, ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``layer_types``,
    ``sliding_window``, ``rope_theta``, ``rms_norm_eps``,
    ``intermediate_size``, ``num_dense_layers``, ``moe_intermediate_size``,
    ``num_experts_per_tok``, ``route_norm``, ``route_scale``,
    ``load_balance_coeff``, ``mup_enabled``, ``vocab_size``,
    ``num_hidden_layers``), where ``num_experts`` counts the experts HELD
    here, ``router_width`` all that the router scores (default: the same)
    and ``first_expert`` the first one held.  One shared expert, as every
    published model of the family has.

    data: (N, T) token ids; softmax_label: (N, T) next-token ids."""
    cfg = dict(cfg)
    cfg.setdefault("router_width", cfg["num_experts"])
    if cfg.get("num_shared_experts", 1) != 1:
        raise ValueError("afmoe: one shared expert, got %r"
                         % cfg["num_shared_experts"])
    hidden, vocab = cfg["hidden_size"], cfg["vocab_size"]
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data, input_dim=vocab, output_dim=hidden,
                      name="tok_embed")
    if cfg.get("mup_enabled", False):
        x = x * (float(hidden) ** 0.5)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, cfg, i, flash_min_seq)
    x = sym.RMSNorm(x, eps=cfg["rms_norm_eps"], name="ln_f")
    logits = sym.FullyConnected(x, num_hidden=vocab, flatten=False,
                                no_bias=True, name="head")
    logits = sym.Reshape(logits, shape=(-1, vocab))
    return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,)),
                             name="softmax")
