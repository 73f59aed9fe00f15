"""Plain reference for the decoder-only language models (GPT-2 family):
straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, no
kernels, no cache, no batching tricks.  It imports nothing of the program and
takes nothing the program made; the benchmark hands the same seeded weights
and tokens to both sides.

Follows Radford et al. 2019 (pre-LN decoder, learned positions, GELU FFN) as
``openai-community/gpt2`` configures it, with the departures the
configuration files list under ``assumed``: an output head of its own
(``head_weight``/``head_bias``; GPT-2 ties it to the embedding), biases on
every projection, the exact (erf) GELU.  Parameter names are the ones the
program's training graph uses, so one dict serves both.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.refutil import norms, nudged, seed_key

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def param_shapes(cfg):
    """name -> shape, in the program's naming (models/transformer.py)."""
    h, v, t = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    ffn = cfg["n_inner"] or 4 * h
    shapes = {"tok_embed_weight": (v, h), "pos_embed": (t, h)}
    for i in range(cfg["n_layer"]):
        p = "l%d_" % i
        for nm, shape in (("q", (h, h)), ("k", (h, h)), ("v", (h, h)),
                          ("proj", (h, h)), ("ff1", (ffn, h)),
                          ("ff2", (h, ffn))):
            shapes[p + nm + "_weight"] = shape
            shapes[p + nm + "_bias"] = (shape[0],)
        for ln in ("ln1", "ln2"):
            shapes[p + ln + "_gamma"] = (h,)
            shapes[p + ln + "_beta"] = (h,)
    shapes.update({"ln_f_gamma": (h,), "ln_f_beta": (h,),
                   "head_weight": (v, h), "head_bias": (v,)})
    return shapes


def make_weights(cfg, seed):
    """Every leaf in float32, on the default device, in one jitted call:
    N(0, initializer_range) matrices and embeddings, N(0, range) biases and
    1 + N(0, range) gains, so that no leaf starts at a symmetric point."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    @jax.jit
    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            out[name] = 1.0 + x if name.endswith("_gamma") else x
        return out

    return build(seed_key(seed))


def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _dense(p, name, x, precision):
    return jnp.matmul(x, p[name + "_weight"].T, precision=precision) \
        + p[name + "_bias"]


def forward(p, ids, cfg, precision=HIGHEST, cast=None):
    """Logits (B, T, V) of token ids (B, T).  ``cast`` (a dtype) rounds the
    operands of every matrix product to that type first: the low-precision
    control, never the reference."""
    heads = cfg["n_head"]
    B, T = ids.shape
    h = cfg["n_embd"]
    hd = h // heads

    def mm(name, x):
        if cast is None:
            return _dense(p, name, x, precision)
        y = jnp.matmul(qz(x), qz(p[name + "_weight"]).T, precision=precision)
        return y + p[name + "_bias"]

    def qz(x):
        return x if cast is None else x.astype(cast).astype(jnp.float32)

    x = p["tok_embed_weight"][ids] + p["pos_embed"][None, :T]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(cfg["n_layer"]):
        pre = "l%d_" % i
        a = _ln(x, p[pre + "ln1_gamma"], p[pre + "ln1_beta"])
        q = mm(pre + "q", a).reshape(B, T, heads, hd)
        k = mm(pre + "k", a).reshape(B, T, heads, hd)
        v = mm(pre + "v", a).reshape(B, T, heads, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", qz(q), qz(k),
                       precision=precision) * (1.0 / math.sqrt(hd))
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum("bhqk,bkhd->bqhd", qz(w), qz(v),
                         precision=precision).reshape(B, T, h)
        x = x + mm(pre + "proj", att)
        f = _ln(x, p[pre + "ln2_gamma"], p[pre + "ln2_beta"])
        f = jax.nn.gelu(mm(pre + "ff1", f), approximate=False)
        x = x + mm(pre + "ff2", f)
    x = _ln(x, p["ln_f_gamma"], p["ln_f_beta"])
    return mm("head", x)


def summed_loss(p, ids, labels, cfg, cast=None):
    """(sum of the tokens' cross-entropies, sum of the softmax outputs).
    The first is what the program's SoftmaxOutput head differentiates
    (normalization 'null'); the second is what its step returns as 'loss'."""
    logits = forward(p, ids, cfg, cast=cast)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.sum(picked), jnp.sum(jnp.exp(logp))


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items, cast):
    cfg = dict(cfg_items)

    @jax.jit
    def fn(p, ids, labels):
        (ce, probs), g = jax.value_and_grad(
            lambda p_: summed_loss(p_, ids, labels, cfg, cast=cast),
            has_aux=True)(p)
        return ce, probs, g

    return fn


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, type(None)))))


def loss_and_grads(p, batch, cfg, rows_per_block, cast=None):
    """Summed loss and its gradient over one batch, ``rows_per_block``
    sequences at a time (the loss is a sum over rows, so the blocks add
    exactly) so that float32 activations fit beside nothing else."""
    fn = _grad_fn(_hashable(cfg), cast)
    ids = np.asarray(batch["data"]).astype(np.int32)
    labels = np.asarray(batch["softmax_label"]).astype(np.int32)
    ce = probs = 0.0
    grads = None
    for lo in range(0, ids.shape[0], rows_per_block):
        c, s, g = fn(p, ids[lo:lo + rows_per_block],
                     labels[lo:lo + rows_per_block])
        ce, probs = ce + float(c), probs + float(s)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return ce, probs, grads


def train_reference(cfg, seed, store_dtypes, batches, traffic, cast=None,
                    nudge=0.0):
    """The first ``len(batches)`` training steps from the seed, in float32:
    each step's 'loss' as the program's step reports it (the sum of the
    head's softmax outputs), the first gradient's norm by leaf, and the norm
    of each leaf's change over the steps.  Parameters are rounded to the type
    the configuration stores them in after every update; nothing else is."""
    from benchmark.lib.sgd import sgd_momentum
    tr = cfg["training"]
    w = make_weights(cfg, seed)
    p0 = {k: v.astype(store_dtypes[k]).astype(jnp.float32)
          for k, v in w.items()}
    del w
    if nudge:       # calibrate.py's look at the model's own sensitivity
        p0 = nudged(p0, nudge, seed)
    p = p0
    m = {k: jnp.zeros_like(v) for k, v in p.items()}
    out = {"loss": [], "cross_entropy": []}
    for i, batch in enumerate(batches):
        ce, probs, g = loss_and_grads(p, batch, cfg,
                                      traffic["reference_rows_per_block"],
                                      cast=cast)
        if i == 0:
            out["grad_norms"] = norms(g)
        out["loss"].append(probs)
        out["cross_entropy"].append(ce)
        p, m = sgd_momentum(p, m, g, tr["lr"], tr["momentum"], tr["wd"],
                            store_dtypes)
        del g
    out["change_norms"] = norms({k: p[k] - p0[k] for k in p})
    return out


def served_token_gap(cfg, seed, sample, rows_per_block, cast=None):
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``sample`` (a list of
    (prompt ids, served ids)): one full forward pass per request over the
    prompt with its served tokens, padded to the context length (causal, so
    the padding is never seen).  With ``cast`` (the control) the tokens judged
    are the ones the low-precision pass puts first at each position instead.
    Returns (gap, tokens judged, where the widest was)."""
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
        got = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
        return best - got

    worst, n_tokens, where = 0.0, 0, None
    for lo in range(0, len(sample), rows_per_block):
        block = sample[lo:lo + rows_per_block]
        ids = np.zeros((rows_per_block, T), np.int32)
        nxt = np.zeros((rows_per_block, T), np.int32)
        mask = np.zeros((rows_per_block, T), bool)
        for r, (prompt, served) in enumerate(block):
            seq = np.concatenate([prompt, served])[:T]
            ids[r, :len(seq)] = seq
            # position i's logits choose token i + 1
            first = len(prompt) - 1
            last = min(first + len(served), T)
            nxt[r, first:last] = served[:last - first]
            mask[r, first:last] = True
        g = np.where(mask, np.asarray(gaps(p, ids, nxt)), 0.0)
        n_tokens += int(mask.sum())
        if not np.all(np.isfinite(g)):
            return float("inf"), n_tokens, "a non-finite logit"
        if g.max() > worst:
            r, i = np.unravel_index(int(np.argmax(g)), g.shape)
            worst = float(g.max())
            where = "request %d position %d" % (lo + r, i)
    return worst, n_tokens, where
