"""Plain reference for the Sarvam-MLA decoder (``model_type: sarvam_mla``,
sarvamai/sarvam-105b): straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision, latent attention in its EXPANDED form (keys and values
up-projected for every position), no cache, no kernels, no sorting: every held
expert over every token, weighted by a ``w`` that is 0 where it was not picked.
It imports nothing of the program and takes nothing the program made; the
benchmark hands the same seeded weights and tokens to both sides.

The layer, as the configuration file's ``assumed`` lists it beside what
``config.json`` pins (d hidden, H heads, q = [q_n (nope) ; q_r (rope)], latent
c of ``kv_lora_rank``, one rope key for all heads, ``rms(x; g) = x /
sqrt(mean(x^2) + eps) * g``), for the row at position p:

    h         = rms(x; g_in);   q = W_q h  (H x (nope + rope));  q_r <- rope(q_r, p)
    [c' ; k'] = W_kva h;        c = rms(c'; g_kv);               k_r = rope(k', p)
    k_n[s,i]  = W_UK,i c_s;     v[s,i] = W_UV,i c_s              (W_kv_b = [W_UK,i ; W_UV,i] by head)
    a[p,s,i]  = softmax_{s <= p} sigma (q_n,i . k_n[s,i] + q_r,i . k_r,s);   o_i = sum_s a v[s,i]
    x         = x + W_o concat_i(o_i);    h2 = rms(x; g_post)
    dense:    x = x + W_2 (silu(W_1 h2) * W_3 h2)
    experts:  s = sigmoid(W_r h2);  S = top-k of s + b;  w = scale * s_S / sum_S s
              x = x + shared(h2) + sum_{e in S, e held} w_e expert_e(h2)
    logits    = W_head rms(x; g_f)

rope turns the pairs (j, j + rope / 2) by ``p * inv_freq_j``, the frequencies
YaRN's (``deepseek_yarn``): f_j = theta^(-2j / rope), the pairs between
``lo = floor(corr(beta_fast))`` and ``hi = ceil(corr(beta_slow))`` ramped from
f_j to f_j / factor, corr(b) = rope ln(original / (2 pi b)) / (2 ln theta);
sigma = (nope + rope)^(-1/2) m^2, m = 0.1 mscale_all_dim ln(factor) + 1.

**The share.**  ``num_experts`` of the configuration counts the experts HELD
(``first_expert`` onward) of the ``router_width`` that are scored; the
vocabulary is the slice the configuration states.

Parameters are kept in the type the configuration serves them in (their values
are representable in it: the program gets the same numbers) and widened a
layer at a time, so that a request of 8,192 positions fits beside them: 5.3 GB
at the benchmark's size where float32 copies would take 10.6.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.refutil import seed_key

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512       # queries whose scores are alive at once


def _is_expert_layer(cfg, i):
    return i >= cfg["first_k_dense_replace"]


def param_shapes(cfg):
    """name -> shape, in the program's naming (models/sarvam_mla.py)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    lat, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    F, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, E = cfg["num_experts"], cfg.get("router_width", cfg["num_experts"])
    fs = f * cfg["num_shared_experts"]
    vocab = cfg["vocab_size"]
    shapes = {"tok_embed_weight": (vocab, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d_" % i
        shapes.update({
            p + "ln1_gamma": (d,), p + "q_weight": (H * (nope + rope), d),
            p + "kva_weight": (lat + rope, d), p + "kvn_gamma": (lat,),
            p + "kvb_weight": (H * (nope + v), lat),
            p + "proj_weight": (d, H * v), p + "ln2_gamma": (d,)})
        if _is_expert_layer(cfg, i):
            p += "moe_"
            shapes.update({
                p + "router_weight": (d, E), p + "shared_w1": (d, fs),
                p + "shared_w3": (d, fs), p + "shared_w2": (fs, d),
                p + "expert_w1": (held, d, f), p + "expert_w3": (held, d, f),
                p + "expert_w2": (held, f, d)})
        else:
            shapes.update({p + "ff1_weight": (F, d), p + "ff3_weight": (F, d),
                           p + "ff2_weight": (d, F)})
    shapes.update({"ln_f_gamma": (d,), "head_weight": (vocab, d)})
    return shapes


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "gain"))
def _leaf(key, std, *, shape, dtype, gain):
    x = std * jax.random.normal(key, shape, F32)
    return 1.0 + x if gain else x.astype(dtype)


def make_weights(cfg, seed):
    """N(0, initializer_range) matrices and embeddings in the serving dtype
    (so their values are the ones the program holds), gains 1 + N(0, range)
    in float32.  A leaf a call, on the default device: 2.66 G values at the
    benchmark's size, a few seconds on the chip."""
    dtype = jnp.dtype(cfg["serving"]["dtype"])
    key = seed_key(seed)
    return {name: _leaf(jax.random.fold_in(key, i),
                        cfg["initializer_range"], shape=shape, dtype=dtype,
                        gain=name.endswith("_gamma"))
            for i, (name, shape) in enumerate(param_shapes(cfg).items())}


def inv_freq(cfg):
    """YaRN's rotary inverse frequencies of the rope lanes: (rope / 2,)."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    gamma = 1.0 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return ((1.0 - gamma) * f / rs["factor"] + gamma * f).astype(np.float32)


def score_scale(cfg):
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, pos, freqs):
    """x (T, ..., rope) turned by pos (T,), pairs (j, j + rope / 2)."""
    ang = pos.astype(F32)[:, None] * freqs
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
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
    """The expert layer's branch for tokens ``h2`` (T, d): every held expert
    over every token, weighted by 0 where it was not picked, and the shared
    expert.  ``pre``: the layer's parameter prefix (``l3_moe_``)."""
    qz = _quantizer(cast)
    T = h2.shape[0]
    first = cfg.get("first_expert", 0)
    s = jax.nn.sigmoid(jnp.matmul(qz(h2), qz(p[pre + "router_weight"]),
                                  precision=HIGHEST))
    _, picks = jax.lax.top_k(s + p.get(pre + "expert_bias", 0.0),
                             cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, picks, axis=-1)
    w = cfg["routed_scaling_factor"] * w / jnp.sum(w, -1, keepdims=True)
    dense_w = jnp.zeros_like(s).at[jnp.arange(T)[:, None], picks].set(w)

    def one_expert(e, acc):
        y = _gated(qz, h2, p[pre + "expert_w1"][e], p[pre + "expert_w3"][e],
                   p[pre + "expert_w2"][e])
        return acc + jax.lax.dynamic_index_in_dim(
            dense_w, first + e, axis=1, keepdims=True) * y

    out = jax.lax.fori_loop(0, cfg["num_experts"], one_expert,
                            jnp.zeros_like(h2, F32))
    if cfg["num_shared_experts"]:
        out = out + _gated(qz, h2, p[pre + "shared_w1"],
                           p[pre + "shared_w3"], p[pre + "shared_w2"])
    return out


def forward(p, ids, cfg, cast=None):
    """Logits (T, V) of one sequence of token ids (T,).  ``cast`` (a dtype)
    rounds the operands of every matrix product to that type first: the
    low-precision control, never the reference."""
    H = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    lat, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    eps, T = cfg["rms_norm_eps"], ids.shape[0]
    freqs, sigma = inv_freq(cfg), score_scale(cfg)
    pos = jnp.arange(T, dtype=jnp.int32)
    block = min(QUERY_BLOCK, T)
    if T % block:
        raise ValueError("the reference attends %d queries at a time: pad "
                         "%d positions to a multiple" % (block, T))

    qz = _quantizer(cast)

    def mm(x, w):                       # x (.., in) by w (out, in)
        return jnp.matmul(qz(x), qz(w).T, precision=HIGHEST)

    x = p["tok_embed_weight"][ids].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d_" % i
        h = _rms(x, p[pre + "ln1_gamma"], eps)
        q = mm(h, p[pre + "q_weight"]).reshape(T, H, nope + rope)
        q_n, q_r = q[..., :nope], _rope(q[..., nope:], pos, freqs)
        ckv = mm(h, p[pre + "kva_weight"])
        c = _rms(ckv[:, :lat], p[pre + "kvn_gamma"], eps)
        k_r = _rope(ckv[:, lat:], pos, freqs)                   # (T, rope)
        kv = mm(c, p[pre + "kvb_weight"]).reshape(T, H, nope + v)
        k_n, val = kv[..., :nope], kv[..., nope:]

        def attend(lo):
            rows = lo + jnp.arange(block)
            qn = jax.lax.dynamic_slice_in_dim(q_n, lo, block)
            qr = jax.lax.dynamic_slice_in_dim(q_r, lo, block)
            s = (jnp.einsum("qhd,khd->hqk", qz(qn), qz(k_n),
                            precision=HIGHEST)
                 + jnp.einsum("qhd,kd->hqk", qz(qr), qz(k_r),
                              precision=HIGHEST)) * sigma
            s = jnp.where(pos[None, None, :] <= rows[None, :, None], s,
                          -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", qz(jax.nn.softmax(s, axis=-1)),
                              qz(val), precision=HIGHEST)

        o = jax.lax.map(attend, jnp.arange(0, T, block))
        x = x + mm(o.reshape(T, H * v), p[pre + "proj_weight"])
        h2 = _rms(x, p[pre + "ln2_gamma"], eps)
        if not _is_expert_layer(cfg, i):
            a = mm(h2, p[pre + "ff1_weight"])
            b = mm(h2, p[pre + "ff3_weight"])
            x = x + mm(jax.nn.silu(a) * b, p[pre + "ff2_weight"])
            continue
        x = x + experts(p, pre + "moe_", h2, cfg, cast)
    return mm(_rms(x, p["ln_f_gamma"], eps), p["head_weight"])


def served_token_gap(cfg, seed, sample, rows_per_block, cast=None):
    """The MEAN gap by which a served token's logit lies below the
    reference's best, over every served token of ``sample`` (a list of
    (prompt ids, served ids)): one full forward pass per request over the
    prompt with its served tokens, padded to the context length (causal, so
    the padding is never seen).  With ``cast`` (the control) the tokens judged
    are the ones the low-precision pass puts first at each position instead.

    The mean and not the widest (``refs/transformer_lm.py``'s): a bfloat16
    router orders its near-tied scores otherwise than this float32 one in
    one token-layer of seven, so a token in ten is served through another
    held expert than the reference's, and the widest gap over thousands of
    tokens reads those picks and not the products (PERF.md section 2 has the
    readings with the reference forced to the program's picks).  The widest
    and the share of tokens that are not the reference's first go into the
    note beside the number.

    Returns (gap, tokens judged, the note).  ``rows_per_block`` is 1 here:
    a request at a time is what fits."""
    del rows_per_block
    T = cfg["n_positions"]
    p = make_weights(cfg, seed)

    @jax.jit
    def gaps(p, ids, lo_ids):
        logits = forward(p, ids, cfg)
        if cast is None:
            chosen = lo_ids
        else:
            chosen = jnp.argmax(forward(p, ids, cfg, cast=cast), axis=-1)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return best - got

    total, worst, n_tokens, n_other, where = 0.0, 0.0, 0, 0, None
    for r, (prompt, served) in enumerate(sample):
        ids = np.zeros(T, np.int32)
        nxt = np.zeros(T, np.int32)
        mask = np.zeros(T, bool)
        seq = np.concatenate([prompt, served])[:T]
        ids[:len(seq)] = seq
        # position i's logits choose token i + 1
        first = len(prompt) - 1
        last = min(first + len(served), T)
        nxt[first:last] = served[:last - first]
        mask[first:last] = True
        g = np.where(mask, np.asarray(gaps(p, ids, nxt)), 0.0)
        n_tokens += int(mask.sum())
        if not np.all(np.isfinite(g)):
            return float("inf"), n_tokens, "a non-finite logit"
        total += float(g.sum(dtype=np.float64))
        n_other += int((g > 0).sum())
        if g.max() > worst:
            worst = float(g.max())
            where = "request %d position %d" % (r, int(np.argmax(g)))
    if not n_tokens:
        return 0.0, 0, None
    return (total / n_tokens, n_tokens,
            "the mean; widest %.4g at %s, %d not the reference's first"
            % (worst, where, n_other))
