"""Plain reference for the LFM2-MoE decoder (``model_type: lfm2_moe``,
LiquidAI/LFM2-8B-A1B): straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision, each convolution over the whole sequence (no state), each
attention layer over the whole sequence (no cache, no kernels), every expert
over every token weighted by a ``w`` that is 0 where it was not picked.  It
imports nothing of the program and takes nothing the program made; the
benchmark hands the same seeded weights and tokens to both sides.

The layer, as the configuration file's ``assumed`` lists it beside what
``config.json`` pins (``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``), for the
row at position t of a sequence:

    h        = rms(x; g_1)
    conv:    [B ; C ; X] = W_in h;   v_t = B_t X_t
             z_t = sum_k w[:, k] v_{t - (L - 1) + k}   (v before 0: zero)
             x = x + W_out (C_t z_t)
    attn:    q = rope(rms_hd(W_q h; g_q));  k = rope(rms_hd(W_k h; g_k));
             v = W_v h;  head i of q attends head i // (H / H_kv) of k, v,
             causal, at hd^-1/2;  x = x + W_o concat(o)
    h2       = rms(x; g_2)
    dense:   x = x + W_2 (silu(W_1 h2) W_3 h2)
    experts: s = sigmoid(W_r h2);  S = top-k of s + b (b = 0);
             w = scale s_S / sum_S s;  x = x + sum_{e in S} w_e expert_e(h2)
    logits   = E rms(x; g_f)                      (the head tied: E the embedding)

rope turns the pairs (j, j + hd / 2) by ``t theta^(-2j / hd)``.

Parameters come as the serving type's values on the host (bfloat16 at the
benchmark's size: 7.9 GB) and go to the device one LAYER at a time, widened
to float32 there, so that the reference never holds more than a layer of
float32 weights (15.7 GB for all of them) beside the checked requests'
activations.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.refutil import seed_key

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512       # queries whose scores are alive at once


def _kinds(cfg):
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _is_expert_layer(cfg, i):
    return i >= cfg["num_dense_layers"]


def param_shapes(cfg):
    """name -> shape, in the program's naming (models/lfm2_moe.py)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Hkv, hd = cfg["num_key_value_heads"], d // H
    F, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, L = cfg["num_experts"], cfg["conv_L_cache"]
    shapes = {"tok_embed_weight": (cfg["vocab_size"], d)}
    for i, kind in enumerate(_kinds(cfg)):
        p = "l%d_" % i
        shapes.update({p + "ln1_gamma": (d,), p + "ln2_gamma": (d,)})
        if kind == "conv":
            shapes.update({p + "in_proj_weight": (3 * d, d),
                           p + "conv_weight": (d, L),
                           p + "out_proj_weight": (d, d)})
        else:
            shapes.update({p + "q_weight": (H * hd, d),
                           p + "k_weight": (Hkv * hd, d),
                           p + "v_weight": (Hkv * hd, d),
                           p + "o_weight": (d, H * hd),
                           p + "q_norm_gamma": (hd,),
                           p + "k_norm_gamma": (hd,)})
        if _is_expert_layer(cfg, i):
            p += "moe_"
            shapes.update({p + "router_weight": (d, E),
                           p + "expert_w1": (E, d, f),
                           p + "expert_w3": (E, d, f),
                           p + "expert_w2": (E, f, d)})
        else:
            shapes.update({p + "ff1_weight": (F, d), p + "ff3_weight": (F, d),
                           p + "ff2_weight": (d, F)})
    shapes["ln_f_gamma"] = (d,)
    return shapes


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "gain"))
def _leaf(key, std, *, shape, dtype, gain):
    x = std * jax.random.normal(key, shape, F32)
    return 1.0 + x if gain else x.astype(dtype)


def make_weights(cfg, seed):
    """N(0, initializer_range) matrices and embeddings in the serving dtype
    (so their values are the ones the program holds), gains 1 + N(0, range)
    in float32: HOST arrays, each leaf made on the default device and
    brought back before the next is made (3.9 G values at the benchmark's
    size: the device holds one leaf of them at a time, the host a copy in
    the serving dtype and never a float32 one)."""
    dtype = jnp.dtype(cfg["serving"]["dtype"])
    key = seed_key(seed)
    return {name: np.asarray(_leaf(jax.random.fold_in(key, i),
                                   cfg["initializer_range"], shape=shape,
                                   dtype=dtype, gain=name.endswith("_gamma")))
            for i, (name, shape) in enumerate(param_shapes(cfg).items())}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, pos, theta):
    """x (T, heads, hd) turned by pos (T,), pairs (j, j + hd / 2)."""
    hd = x.shape[-1]
    freqs = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos.astype(F32)[:, None, None] * jnp.asarray(freqs, F32)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _quantizer(cast):
    def qz(x):
        x = x.astype(F32)
        return x if cast is None else x.astype(cast).astype(F32)
    return qz


def _gated(qz, x, w1, w3, w2):
    """``(silu(x W1) * (x W3)) W2`` for (in, f), (in, f), (f, in)."""
    a = jnp.matmul(qz(x), qz(w1), precision=HIGHEST)
    b = jnp.matmul(qz(x), qz(w3), precision=HIGHEST)
    return jnp.matmul(qz(jax.nn.silu(a) * b), qz(w2), precision=HIGHEST)


def experts(p, pre, h2, cfg, cast=None):
    """The expert layer's branch for tokens ``h2`` (T, d): every expert over
    every token, weighted by 0 where it was not picked.  ``pre``: the
    layer's parameter prefix (``l3_moe_``)."""
    qz = _quantizer(cast)
    T = h2.shape[0]
    s = jax.nn.sigmoid(jnp.matmul(qz(h2), qz(p[pre + "router_weight"]),
                                  precision=HIGHEST))
    _, picks = jax.lax.top_k(s + p.get(pre + "expert_bias", 0.0),
                             cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = cfg["routed_scaling_factor"] * w
    dense_w = jnp.zeros_like(s).at[jnp.arange(T)[:, None], picks].set(w)

    def one_expert(e, acc):
        y = _gated(qz, h2, p[pre + "expert_w1"][e], p[pre + "expert_w3"][e],
                   p[pre + "expert_w2"][e])
        return acc + jax.lax.dynamic_index_in_dim(
            dense_w, e, axis=1, keepdims=True) * y

    return jax.lax.fori_loop(0, cfg["num_experts"], one_expert,
                             jnp.zeros_like(h2, F32))


def layer(p, pre, kind, x, cfg, cast=None):
    """Layer ``pre`` (``l3_``) of one sequence ``x`` (T, d), float32."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Hkv, hd = cfg["num_key_value_heads"], d // cfg["num_attention_heads"]
    eps, T = cfg["norm_eps"], x.shape[0]
    qz = _quantizer(cast)

    def mm(a, w):                       # a (.., in) by w (out, in)
        return jnp.matmul(qz(a), qz(w).T, precision=HIGHEST)

    h = _rms(x, p[pre + "ln1_gamma"], eps)
    if kind == "conv":
        L = cfg["conv_L_cache"]
        bcx = mm(h, p[pre + "in_proj_weight"])
        b, c, xx = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
        v = jnp.pad(b * xx, ((L - 1, 0), (0, 0)))
        w = p[pre + "conv_weight"].astype(F32)
        z = sum(w[:, k] * v[k:k + T] for k in range(L))
        x = x + mm(c * z, p[pre + "out_proj_weight"])
    else:
        pos = jnp.arange(T, dtype=jnp.int32)
        theta = float(cfg["rope_theta"])
        q = _rope(_rms(mm(h, p[pre + "q_weight"]).reshape(T, H, hd),
                       p[pre + "q_norm_gamma"], eps), pos, theta)
        k = _rope(_rms(mm(h, p[pre + "k_weight"]).reshape(T, Hkv, hd),
                       p[pre + "k_norm_gamma"], eps), pos, theta)
        val = mm(h, p[pre + "v_weight"]).reshape(T, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=1)
        val = jnp.repeat(val, H // Hkv, axis=1)
        block = min(QUERY_BLOCK, T)
        if T % block:
            raise ValueError("the reference attends %d queries at a time: "
                             "pad %d positions to a multiple" % (block, T))

        def attend(lo):
            rows = lo + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(q, lo, block)
            s = jnp.einsum("qhd,khd->hqk", qz(qb), qz(k),
                           precision=HIGHEST) * hd ** -0.5
            s = jnp.where(pos[None, None, :] <= rows[None, :, None], s,
                          -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", qz(jax.nn.softmax(s, axis=-1)),
                              qz(val), precision=HIGHEST)

        o = jax.lax.map(attend, jnp.arange(0, T, block))
        x = x + mm(o.reshape(T, H * hd), p[pre + "o_weight"])
    h2 = _rms(x, p[pre + "ln2_gamma"], eps)
    if _is_expert_layer(cfg, int(pre[1:-1])):
        return x + experts(p, pre + "moe_", h2, cfg, cast)
    return x + mm(jax.nn.silu(mm(h2, p[pre + "ff1_weight"]))
                  * mm(h2, p[pre + "ff3_weight"]), p[pre + "ff2_weight"])


def _on_device(p, names):
    """The named host parameters on the device, widened to float32 there."""
    return {k: jnp.asarray(p[k]).astype(F32) for k in names}


def final_hidden(p, ids, cfg, cast=None):
    """``rms(x; g_f)`` after the stack for a batch of sequences ``ids``
    (B, T): (B, T, d) float32 on the device.  The layers go through one at
    a time, each layer's parameters on the device only while it runs; the
    sequences of the batch one after another within a layer."""
    names = param_shapes(cfg)
    x = _on_device(p, ["tok_embed_weight"])["tok_embed_weight"][
        jnp.asarray(ids)]
    runs = {}
    for i, kind in enumerate(_kinds(cfg)):
        pre = "l%d_" % i
        key = (kind, _is_expert_layer(cfg, i))
        if key not in runs:
            def run(lp, x, pre=pre, kind=kind):
                # the same function for every layer of its kind: the
                # layer's names enter through the prefix of its parameters
                return jax.lax.map(lambda xs: layer(lp, pre, kind, xs, cfg,
                                                    cast), x)
            runs[key] = (jax.jit(run, static_argnames=("pre",)), pre)
        run, first = runs[key]
        lp = _on_device(p, [k for k in names if k.startswith(pre)])
        # rename to the first layer of the kind: one trace serves them all
        lp = {first + k[len(pre):]: v for k, v in lp.items()}
        x = run(lp, x, pre=first)
        del lp
    return _rms(x, jnp.asarray(p["ln_f_gamma"], F32), cfg["norm_eps"])


def served_token_gap(cfg, seed, sample, rows_per_block, cast=None):
    """The MEAN gap by which a served token's logit lies below the
    reference's best, over every served token of ``sample`` (a list of
    (prompt ids, served ids)): one full forward pass per request over the
    prompt with its served tokens, padded to the context length (causal, so
    the padding is never seen).  With ``cast`` (the control) the tokens judged
    are the ones the low-precision pass puts first at each position instead.

    The mean and not the widest, as ``refs/sarvam_mla.py`` has it: a
    bfloat16 router orders near-tied scores otherwise than this float32 one,
    so some tokens are served through another expert than the reference's
    and the widest gap over thousands of tokens reads those picks, not the
    products (the workload file's ``why``).  The widest and the count of
    tokens that are not the reference's first go into the note beside the
    number.  Returns (gap, tokens judged, the note).  ``rows_per_block``:
    unused (every request is one row of the batch)."""
    del rows_per_block
    if not sample:
        return 0.0, 0, None
    T = cfg["n_positions"]
    p = make_weights(cfg, seed)
    ids = np.zeros((len(sample), T), np.int32)
    nxt = np.zeros((len(sample), T), np.int32)
    mask = np.zeros((len(sample), T), bool)
    for r, (prompt, served) in enumerate(sample):
        seq = np.concatenate([prompt, served])[:T]
        ids[r, :len(seq)] = seq
        # position i's logits choose token i + 1
        first = len(prompt) - 1
        last = min(first + len(served), T)
        nxt[r, first:last] = served[:last - first]
        mask[r, first:last] = True
    head = jnp.asarray(p["tok_embed_weight"]).astype(F32)
    x = final_hidden(p, ids, cfg)
    x_low = None if cast is None else final_hidden(p, ids, cfg, cast)

    @jax.jit
    def gaps(head, u, u_low, chosen):
        logits = jnp.matmul(u, head.T, precision=HIGHEST)
        if u_low is not None:
            qz = _quantizer(cast)
            chosen = jnp.argmax(jnp.matmul(qz(u_low), qz(head).T,
                                           precision=HIGHEST), axis=-1)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return best - got

    total, worst, n_tokens, n_other, where = 0.0, 0.0, 0, 0, None
    for r in range(len(sample)):
        g = np.where(mask[r], np.asarray(gaps(
            head, x[r], None if x_low is None else x_low[r], nxt[r])), 0.0)
        n_tokens += int(mask[r].sum())
        if not np.all(np.isfinite(g)):
            return float("inf"), n_tokens, "a non-finite logit"
        total += float(g.sum(dtype=np.float64))
        n_other += int((g > 0).sum())
        if g.max() > worst:
            worst = float(g.max())
            where = "request %d position %d" % (r, int(np.argmax(g)))
    return (total / n_tokens, n_tokens,
            "the mean; widest %.4g at %s, %d not the reference's first"
            % (worst, where, n_other))
