"""Plain reference for the AFMoE decoder (Arcee Trinity family, ``model_type:
afmoe``): straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, no kernels, no sorting, no grouped products.  It imports nothing of
the program and takes nothing the program made; the benchmark hands the same
seeded weights and tokens to both sides.

The layer, as the configuration file's ``assumed`` lists it beside what
``config.json`` pins (d hidden, H query heads on G key/value heads of D, window
w, E experts scored, k picked, ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``):

    h0      = Embed[id] * sqrt(d)                                (mup_enabled)
    a       = rms(h; g1);  q, k, v, z = a.Wq, a.Wk, a.Wv, a.Wz
    q, k    = rms_D(q; gq), rms_D(k; gk)                         (per head)
    sliding layers: q, k = rope(q, k; theta, pairs (i, i + D/2)); full: none
    p_ij    = softmax_j(q_i.k_j / sqrt(D)), j <= i and, sliding, i - j < w
    y       = (o * sigmoid(z)).Wo;          h = h + rms(y; g2)
    m       = rms(h; g3)
    dense:    u = (silu(m.W1) * (m.W3)).W2
    experts:  s = sigmoid(m.Wr);  S = top-k of s + b;  c = scale * s / sum_S s
              u = shared(m) + sum_{e in S, e held} c_e * expert_e(m)
    h       = h + rms(u; g4)
    logits  = rms(h; gf).W_head;   loss = summed token cross-entropy
    after a training forward: n_e = tokens with e in S;
                              b_e <- b_e + rate * sign(mean(n) - n_e)

**The share.**  ``num_experts`` of the configuration counts the experts HELD
(``first_expert`` onward) of the ``router_width`` that are scored: routing is
over all of them, the held experts' terms are computed (every held expert
over every token, weighted by a ``c`` that is 0 where it was not picked: no
capacity, nothing dropped), the others' are left out, as the program leaves
them out.  The vocabulary is the slice the configuration states.

So that three steps at 8,192 tokens fit beside the float32 parameters, their
momentum and their gradient (8.5 GB at the benchmark's size): every layer,
every block of queries, every expert and every block of the head's rows is
rematerialised in the backward pass (``jax.checkpoint``), the blocks and the
experts are loops with one body (so the compiled reference is small: it is
compiled in every run), and the first parameters wait on the host for the
last step.  Parameter names are the ones
the program's training graph uses.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.refutil import norms, nudged, seed_key

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512       # queries whose scores are alive at once
HEAD_BLOCK = 2048       # tokens whose logits are alive at once


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _is_expert_layer(cfg, i):
    return i >= cfg["num_dense_layers"]


def param_shapes(cfg):
    """name -> shape, in the program's naming (models/afmoe.py)."""
    d, H, G, D = _dims(cfg)
    v, F, f = cfg["vocab_size"], cfg["intermediate_size"], \
        cfg["moe_intermediate_size"]
    E, held = cfg.get("router_width", cfg["num_experts"]), cfg["num_experts"]
    shapes = {"tok_embed_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d_" % i
        shapes.update({
            p + "ln1_gamma": (d,), p + "q_weight": (H * D, d),
            p + "qn_gamma": (D,), p + "k_weight": (G * D, d),
            p + "kn_gamma": (D,), p + "v_weight": (G * D, d),
            p + "gate_weight": (H * D, d), p + "proj_weight": (d, H * D),
            p + "ln2_gamma": (d,), p + "ln3_gamma": (d,)})
        if _is_expert_layer(cfg, i):
            p += "moe_"
            shapes.update({
                p + "router_weight": (d, E), p + "shared_w1": (d, f),
                p + "shared_w3": (d, f), p + "shared_w2": (f, d),
                p + "expert_w1": (held, d, f), p + "expert_w3": (held, d, f),
                p + "expert_w2": (held, f, d)})
        else:
            shapes.update({p + "ff1_weight": (F, d), p + "ff3_weight": (F, d),
                           p + "ff2_weight": (d, F)})
        shapes["l%d_ln4_gamma" % i] = (d,)
    shapes.update({"ln_f_gamma": (d,), "head_weight": (v, d)})
    return shapes


def aux_shapes(cfg):
    """name -> shape of the selection bias of every expert layer (over all
    experts scored); it starts at 0."""
    E = cfg.get("router_width", cfg["num_experts"])
    return {"l%d_moe_expert_bias" % i: (E,)
            for i in range(cfg["num_hidden_layers"])
            if _is_expert_layer(cfg, i)}


def make_weights(cfg, seed):
    """Every leaf in float32, in one jitted call: N(0, initializer_range)
    matrices and embeddings, gains 1 + N(0, range)."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    @jax.jit
    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            x = std * jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            out[name] = 1.0 + x if name.endswith("_gamma") else x
        return out

    return build(seed_key(seed))


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + F32(eps)) * g


def _rope(x, theta):
    """(T, heads, D): the pairs (i, i + D/2) turned by pos * theta^(-2i/D)."""
    T, _, D = x.shape
    half = D // 2
    freq = F32(theta) ** (-jnp.arange(half, dtype=F32) / F32(half))
    ang = jnp.arange(T, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _gated(x, w1, w3, w2, mm):
    """(silu(x.W1) * (x.W3)).W2 with W1, W3 (d, f) and W2 (f, d)."""
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def _attention(q, k, v, window, qz):
    """q (T, H, D), k and v (T, G, D): causal, inside ``window`` if it is
    not 0, query head n on key/value head n // (H // G).  A block of queries
    at a time (one loop body, so the program stays small) against a stretch
    of keys of one length: all of them, or with a window the ``window +
    block`` that end where the block ends; the mask goes by position."""
    T, H, D = q.shape
    G = k.shape[1]
    scale = F32(1.0 / math.sqrt(D))
    bq = min(QUERY_BLOCK, T)
    if T % bq:
        raise ValueError("the reference takes T a multiple of %d" % bq)
    span = min(window + bq, T) if window else T

    @jax.checkpoint
    def block(q0):
        k0 = jnp.clip(q0 + bq - span, 0, T - span)
        qb = jax.lax.dynamic_slice_in_dim(q, q0, bq)
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span)
        s = jnp.einsum("qgrd,kgd->grqk", qz(qb.reshape(bq, G, H // G, D)),
                       qz(kb), precision=HIGHEST) * scale
        gap = (q0 + jnp.arange(bq, dtype=jnp.int32))[:, None] \
            - (k0 + jnp.arange(span, dtype=jnp.int32))[None, :]
        seen = gap >= 0
        if window:
            seen = seen & (gap < window)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", qz(w), qz(vb),
                          precision=HIGHEST).reshape(bq, H, D)

    starts = jnp.arange(0, T, bq, dtype=jnp.int32)
    return jax.lax.map(block, starts).reshape(T, H, D)


def _experts(p, pre, m, bias, cfg, mm):
    """The share's expert layer over tokens m (T, d): (u, load)."""
    E = cfg.get("router_width", cfg["num_experts"])
    first, held = cfg.get("first_expert", 0), cfg["num_experts"]
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(mm(m, p[pre + "router_weight"]))         # (T, E)
    _, picks = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    picked = jnp.sum(jax.nn.one_hot(picks, E, dtype=F32), axis=1)   # 0 / 1
    c = s * picked
    if cfg["route_norm"]:
        c = c / (jnp.sum(c, -1, keepdims=True) + F32(1e-20))
    c = c * F32(cfg["route_scale"])
    u = _gated(m, p[pre + "shared_w1"], p[pre + "shared_w3"],
               p[pre + "shared_w2"], mm)

    @jax.checkpoint
    def one(u, held_expert):    # every held expert over every token
        c_e, w1, w3, w2 = held_expert
        return u + c_e[:, None] * _gated(m, w1, w3, w2, mm), None

    u, _ = jax.lax.scan(one, u, (
        c[:, first:first + held].T, p[pre + "expert_w1"],
        p[pre + "expert_w3"], p[pre + "expert_w2"]))
    return u, jnp.sum(picked, axis=0)


def _layer(p, h, bias, *, i, cfg, mm, qz):
    """One decoder layer over h (T, d), ``p`` its own leaves: (h, load or
    None)."""
    d, H, G, D = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    pre = "l%d_" % i
    T = h.shape[0]
    sliding = cfg["layer_types"][i] == "sliding_attention"

    def dense(name, x):         # the program's FullyConnected: (out, in)
        return mm(x, p[pre + name + "_weight"].T)

    a = _rms(h, p[pre + "ln1_gamma"], eps)
    q = _rms(dense("q", a).reshape(T, H, D), p[pre + "qn_gamma"], eps)
    k = _rms(dense("k", a).reshape(T, G, D), p[pre + "kn_gamma"], eps)
    v = dense("v", a).reshape(T, G, D)
    z = dense("gate", a)
    if sliding:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    o = _attention(q, k, v, cfg["sliding_window"] if sliding else 0, qz)
    y = dense("proj", o.reshape(T, H * D) * jax.nn.sigmoid(z))
    h = h + _rms(y, p[pre + "ln2_gamma"], eps)
    m = _rms(h, p[pre + "ln3_gamma"], eps)
    load = None
    if _is_expert_layer(cfg, i):
        u, load = _experts(p, pre + "moe_", m, bias, cfg, mm)
    else:
        u = mm(jax.nn.silu(dense("ff1", m)) * dense("ff3", m),
               p[pre + "ff2_weight"].T)
    return h + _rms(u, p[pre + "ln4_gamma"], eps), load


def _sequence_loss(p, aux, ids, labels, cfg, cast):
    """One sequence (T,): (summed cross-entropy, (sum of the softmax
    outputs, {layer's bias name: load (E,)}))."""
    def qz(x):
        return x if cast is None else x.astype(cast).astype(F32)

    def mm(x, w):
        return jnp.matmul(qz(x), qz(w), precision=HIGHEST)

    d = cfg["hidden_size"]
    h = p["tok_embed_weight"][ids]
    if cfg.get("mup_enabled", False):
        h = h * F32(math.sqrt(d))
    loads = {}
    for i in range(cfg["num_hidden_layers"]):
        name = "l%d_moe_expert_bias" % i
        layer = jax.checkpoint(functools.partial(
            _layer, i=i, cfg=cfg, mm=mm, qz=qz))
        h, load = layer({k: v for k, v in p.items()
                         if k.startswith("l%d_" % i)}, h, aux.get(name))
        if load is not None:
            loads[name] = jax.lax.stop_gradient(load)
    x = _rms(h, p["ln_f_gamma"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def head(block):
        xb, lb = block
        logp = jax.nn.log_softmax(mm(xb, p["head_weight"].T), axis=-1)
        picked = jnp.take_along_axis(logp, lb[:, None], axis=-1)
        return -jnp.sum(picked), jnp.sum(jnp.exp(logp))

    T = x.shape[0]
    rows = math.gcd(T, HEAD_BLOCK)
    ce, probs = jax.lax.map(head, (x.reshape(T // rows, rows, d),
                                   labels.reshape(T // rows, rows)))
    return jnp.sum(ce), (jnp.sum(probs), loads)


def summed_loss(p, ids, labels, cfg, cast=None, aux=None):
    """(sum of the tokens' cross-entropies, sum of the softmax outputs) over
    a batch (B, T), each sequence apart.  The first is what the program's
    SoftmaxOutput head differentiates; the second what its step returns as
    'loss'.  ``aux``: the expert layers' selection biases (default 0)."""
    if aux is None:
        aux = {k: jnp.zeros(s, F32) for k, s in aux_shapes(cfg).items()}
    ce = probs = F32(0.0)
    for b in range(ids.shape[0]):
        c, (s, _loads) = _sequence_loss(p, aux, ids[b], labels[b], cfg, cast)
        ce, probs = ce + c, probs + s
    return ce, probs


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items, cast):
    cfg = {k: (list(v) if isinstance(v, tuple) else v) for k, v in cfg_items}

    @jax.jit
    def fn(p, aux, ids, labels):
        (ce, (probs, loads)), g = jax.value_and_grad(
            lambda p_: _sequence_loss(p_, aux, ids, labels, cfg, cast),
            has_aux=True)(p)
        return ce, probs, loads, g

    return fn


def _hashable(cfg):
    items = []
    for k, v in sorted(cfg.items()):
        if isinstance(v, (int, float, str, type(None))):
            items.append((k, v))
        elif isinstance(v, list) and all(isinstance(x, str) for x in v):
            items.append((k, tuple(v)))
    return tuple(items)


def loss_and_grads(p, aux, batch, cfg, cast=None):
    """Summed loss, its gradient and the new selection biases over one
    batch, one sequence at a time (the loss is a sum over sequences, so they
    add exactly; the loads add too, as the program counts the whole batch's
    tokens).  A batch of no rows (the planted half of a batch of one) gives
    zeros."""
    fn = _grad_fn(_hashable(cfg), cast)
    ids = np.asarray(batch["data"]).astype(np.int32)
    labels = np.asarray(batch["softmax_label"]).astype(np.int32)
    ce = probs = 0.0
    grads = {k: jnp.zeros(v.shape, F32) for k, v in p.items()}
    loads = {k: jnp.zeros_like(v) for k, v in aux.items()}
    for b in range(ids.shape[0]):
        c, s, n, g = fn(p, aux, ids[b], labels[b])
        ce, probs = ce + float(c), probs + float(s)
        grads = {k: grads[k] + g[k] for k in grads}
        loads = {k: loads[k] + n[k] for k in loads}
    rate = F32(cfg["load_balance_coeff"])
    if ids.shape[0]:
        aux = {k: b + rate * jnp.sign(jnp.mean(loads[k]) - loads[k])
               for k, b in aux.items()}
    return ce, probs, grads, aux


def train_reference(cfg, seed, store_dtypes, batches, traffic, cast=None,
                    nudge=0.0):
    """The first ``len(batches)`` training steps from the seed, in float32:
    each step's 'loss' as the program's step reports it (the sum of the
    head's softmax outputs), the first gradient's norm by leaf, and the norm
    of each leaf's change over the steps.  Parameters are rounded to the type
    the configuration stores them in after every update (and are kept in it:
    the rounded value is exact there); the selection biases follow the
    program's rule from step to step; nothing else is rounded."""
    from benchmark.lib.sgd import sgd_momentum
    tr = cfg["training"]
    w = make_weights(cfg, seed)
    p = {k: v.astype(store_dtypes[k]).astype(F32) for k, v in w.items()}
    del w
    if nudge:       # calibrate.py's look at the model's own sensitivity
        p = nudged(p, nudge, seed)
    p0 = {k: np.asarray(v) for k, v in p.items()}       # on the host
    m = {k: jnp.zeros(v.shape, F32) for k, v in p.items()}
    aux = {k: jnp.zeros(s, F32) for k, s in aux_shapes(cfg).items()}
    out = {"loss": [], "cross_entropy": []}
    for i, batch in enumerate(batches):
        ce, probs, g, aux = loss_and_grads(p, aux, batch, cfg, cast=cast)
        if i == 0:
            out["grad_norms"] = norms(g)
        out["loss"].append(probs)
        out["cross_entropy"].append(ce)
        for k in list(p):       # leaf by leaf: the gradient goes as it is used
            new_p, new_m = sgd_momentum(
                {k: p[k]}, {k: m[k]}, {k: g.pop(k)}, tr["lr"],
                tr["momentum"], tr["wd"], store_dtypes)
            p[k], m[k] = new_p[k], new_m[k]
    del m
    out["change_norms"] = {}
    for k in list(p):
        out["change_norms"].update(norms({k: p.pop(k) - p0[k]}))
    return out
