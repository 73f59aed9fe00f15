"""Operations and bytes the algorithms require, from the configuration's
shapes alone: 2 FLOPs per multiply-add, the causal half of attention only,
nothing recomputed.  No function here looks at the implementation, so a share
of the peak reads the same work whatever kernel does it.
"""


def lm_forward_flops_per_token(cfg, head=True):
    """Matrix work of one token through the decoder stack (attention's
    context-dependent part is apart): q, k, v, proj (4 h^2) and the FFN
    (2 * h * ffn) per layer, and the vocabulary head."""
    h, ffn = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    per_layer = 2 * (4 * h * h + 2 * h * ffn)
    return cfg["n_layer"] * per_layer + (2 * h * cfg["vocab_size"]
                                         if head else 0)


def lm_attention_forward_flops(cfg, context):
    """Scores and weighted sum of ONE query against ``context`` keys, all
    layers: 2 * context * h each."""
    return cfg["n_layer"] * 4 * context * cfg["n_embd"]


def lm_train_flops_per_step(cfg, batch, seq):
    """Forward + backward (twice the forward) of ``batch`` sequences of
    ``seq`` tokens; causal attention counts T(T+1)/2 query-key pairs."""
    pairs = seq * (seq + 1) // 2
    fwd = batch * (seq * lm_forward_flops_per_token(cfg)
                   + cfg["n_layer"] * 4 * pairs * cfg["n_embd"])
    return 3 * fwd


def flash_train_flops_bytes(cfg, batch, seq, itemsize=2):
    """Attention alone over one training step, all layers: the forward's two
    matrix products and the backward's four (dV, dP, dQ, dK) over the causal
    pairs; bytes are one read of q, k, v (forward) and q, k, v, o, do
    (backward) and one write of o, dq, dk, dv.  The logsumexp rows and the
    backward's recomputed scores are not counted."""
    h, layers = cfg["n_embd"], cfg["n_layer"]
    pairs = seq * (seq + 1) // 2
    flops = layers * batch * 6 * 2 * pairs * h
    tensors = 3 + 1 + 5 + 3
    bytes_ = layers * tensors * batch * seq * h * itemsize
    return flops, bytes_


def decode_attention_bytes(cfg, attended_tokens, itemsize=4):
    """K and V of every context position a decode step attends, all layers:
    ``attended_tokens`` is the sum of the slots' sequence lengths."""
    return cfg["n_layer"] * 2 * attended_tokens * cfg["n_embd"] * itemsize


def conv_flops(out_h, out_w, kh, kw, cin, cout):
    return 2 * out_h * out_w * kh * kw * cin * cout


def resnet_v2_forward_flops(cfg):
    """One image through the pre-activation bottleneck ResNet the reference's
    ``symbols/resnet.py`` builds for ImageNet: convolutions and the
    classifier (batch norm, ReLU, pooling and adds are not counted)."""
    units = cfg["units"]
    filters = cfg["filter_list"]
    side = cfg["image_shape"][1]
    total = 0
    side = side // 2                                   # conv0 7x7 stride 2
    total += conv_flops(side, side, 7, 7, cfg["image_shape"][0], filters[0])
    side = side // 2                                   # 3x3 max pool stride 2
    cin = filters[0]
    for stage, n_units in enumerate(units):
        cout = filters[stage + 1]
        mid = cout // 4
        for unit in range(n_units):
            stride = 1 if (stage == 0 or unit > 0) else 2
            out = side // stride
            total += conv_flops(side, side, 1, 1, cin, mid)
            total += conv_flops(out, out, 3, 3, mid, mid)
            total += conv_flops(out, out, 1, 1, mid, cout)
            if unit == 0:                              # projection shortcut
                total += conv_flops(out, out, 1, 1, cin, cout)
            cin, side = cout, out
    total += 2 * cin * cfg["num_classes"]
    return total


def resnet_train_flops_per_step(cfg, batch):
    return 3 * batch * resnet_v2_forward_flops(cfg)
