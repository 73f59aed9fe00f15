#!/usr/bin/env python
"""Hand-written pure-JAX ResNet-50 train step — the "ideal program"
yardstick for bench.py (PERF.md).  No framework code: raw jax.numpy +
lax convs in NHWC, bf16 params/activations with fp32 BN stats, fused
fwd+bwd+SGD(momentum+wd) step with full buffer donation.  Methodology
matches bench.py exactly: warmup, 100-iter chain, block_until_ready.

BENCH_ARCH=v2 (default) mirrors the framework bench's architecture
EXACTLY (models/resnet.py: pre-activation v2, data-BN stem, eps=2e-5)
so framework-vs-ideal deltas measure the framework, not the model;
BENCH_ARCH=v1 keeps the classic post-activation network.

Usage: python tools/bench_ideal.py            # bs32 bf16
       BENCH_BATCH=128 python tools/bench_ideal.py
Prints one JSON line {"metric": "resnet50_ideal_img_per_sec", ...}.
BENCH_DUMP_HLO=/path.txt additionally dumps the optimized HLO.
"""
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BOTTLENECK = [3, 4, 6, 3]
WIDTHS = [256, 512, 1024, 2048]
ARCH = os.environ.get("BENCH_ARCH", "v2")
EPS = 2e-5 if ARCH == "v2" else 1e-5


def conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def bn(x, scale, bias, mean, var, momentum=0.9, eps=EPS, train=True):
    """Returns (y, new_mean, new_var); stats in fp32."""
    if train:
        m = jnp.mean(x.astype(jnp.float32), axis=(0, 1, 2))
        v = jnp.var(x.astype(jnp.float32), axis=(0, 1, 2))
        new_mean = momentum * mean + (1 - momentum) * m
        new_var = momentum * var + (1 - momentum) * v
    else:
        m, v, new_mean, new_var = mean, var, mean, var
    inv = lax.rsqrt(v + eps) * scale
    y = (x.astype(jnp.float32) - m) * inv + bias
    return y.astype(x.dtype), new_mean, new_var


def init_params(key, dtype=jnp.bfloat16):
    params, stats = {}, {}
    rngs = iter(jax.random.split(key, 200))

    def conv_p(name, kh, kw, cin, cout):
        fan = kh * kw * cin
        params[name] = (jax.random.normal(next(rngs), (kh, kw, cin, cout),
                                          jnp.float32)
                        * np.sqrt(2.0 / fan)).astype(dtype)

    def bn_p(name, c):
        params[name + "_g"] = jnp.ones((c,), jnp.float32)
        params[name + "_b"] = jnp.zeros((c,), jnp.float32)
        stats[name + "_m"] = jnp.zeros((c,), jnp.float32)
        stats[name + "_v"] = jnp.ones((c,), jnp.float32)

    if ARCH == "v2":
        bn_p("bn_data", 3)
        conv_p("stem", 7, 7, 3, 64)
        bn_p("bn0", 64)
        cin = 64
        for s, (n, w) in enumerate(zip(BOTTLENECK, WIDTHS)):
            for u in range(n):
                pre = "s%du%d" % (s, u)
                mid = w // 4
                bn_p(pre + "_bn1", cin)
                conv_p(pre + "_c1", 1, 1, cin, mid)
                bn_p(pre + "_bn2", mid)
                conv_p(pre + "_c2", 3, 3, mid, mid)
                bn_p(pre + "_bn3", mid)
                conv_p(pre + "_c3", 1, 1, mid, w)
                if u == 0:
                    conv_p(pre + "_sc", 1, 1, cin, w)
                cin = w
        bn_p("bn1", 2048)
    else:
        conv_p("stem", 7, 7, 3, 64)
        bn_p("stem_bn", 64)
        cin = 64
        for s, (n, w) in enumerate(zip(BOTTLENECK, WIDTHS)):
            for u in range(n):
                pre = "s%du%d" % (s, u)
                mid = w // 4
                conv_p(pre + "_c1", 1, 1, cin, mid)
                bn_p(pre + "_bn1", mid)
                conv_p(pre + "_c2", 3, 3, mid, mid)
                bn_p(pre + "_bn2", mid)
                conv_p(pre + "_c3", 1, 1, mid, w)
                bn_p(pre + "_bn3", w)
                if u == 0:
                    conv_p(pre + "_sc", 1, 1, cin, w)
                    bn_p(pre + "_scbn", w)
                cin = w
    params["fc_w"] = (jax.random.normal(next(rngs), (2048, 1000), jnp.float32)
                      * 0.01).astype(dtype)
    params["fc_b"] = jnp.zeros((1000,), jnp.float32)
    return params, stats


def forward(params, stats, x, train=True):
    new_stats = {}

    def run_bn(name, x, fix_gamma=False):
        g = (jnp.ones_like(params[name + "_g"]) if fix_gamma
             else params[name + "_g"])
        y, m, v = bn(x, g, params[name + "_b"],
                     stats[name + "_m"], stats[name + "_v"], train=train)
        new_stats[name + "_m"], new_stats[name + "_v"] = m, v
        return y

    if ARCH == "v2":
        # mirror models/resnet.py resnet(): Cast(bf16) then pre-act v2
        x = x.astype(jnp.bfloat16)
        x = run_bn("bn_data", x, fix_gamma=True)
        x = conv(x, params["stem"], 2)
        x = jax.nn.relu(run_bn("bn0", x))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        for s, (n, w) in enumerate(zip(BOTTLENECK, WIDTHS)):
            for u in range(n):
                pre = "s%du%d" % (s, u)
                stride = 2 if (u == 0 and s > 0) else 1
                act1 = jax.nn.relu(run_bn(pre + "_bn1", x))
                y = conv(act1, params[pre + "_c1"])
                y = jax.nn.relu(run_bn(pre + "_bn2", y))
                y = conv(y, params[pre + "_c2"], stride)
                y = jax.nn.relu(run_bn(pre + "_bn3", y))
                y = conv(y, params[pre + "_c3"])
                sc = x if u != 0 else conv(act1, params[pre + "_sc"], stride)
                x = y + sc
        x = jax.nn.relu(run_bn("bn1", x))
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        logits = x @ params["fc_w"].astype(jnp.float32) + params["fc_b"]
        return logits, new_stats

    x = conv(x, params["stem"], 2)
    x = jax.nn.relu(run_bn("stem_bn", x))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    cin = 64
    for s, (n, w) in enumerate(zip(BOTTLENECK, WIDTHS)):
        for u in range(n):
            pre = "s%du%d" % (s, u)
            stride = 2 if (u == 0 and s > 0) else 1
            y = jax.nn.relu(run_bn(pre + "_bn1",
                                   conv(x, params[pre + "_c1"], stride)))
            y = jax.nn.relu(run_bn(pre + "_bn2", conv(y, params[pre + "_c2"])))
            y = run_bn(pre + "_bn3", conv(y, params[pre + "_c3"]))
            if u == 0:
                x = run_bn(pre + "_scbn", conv(x, params[pre + "_sc"], stride))
            x = jax.nn.relu(x + y)
            cin = w
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    logits = x @ params["fc_w"].astype(jnp.float32) + params["fc_b"]
    return logits, new_stats


def loss_fn(params, stats, x, labels):
    logits, new_stats = forward(params, stats, x)
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, new_stats


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def train_step(params, mom, stats, x, labels):
    (loss, new_stats), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, stats, x, labels)
    lr, mu, wd = 0.1, 0.9, 1e-4
    new_p, new_m = {}, {}
    for k, p in params.items():
        g = grads[k].astype(jnp.float32) + wd * p.astype(jnp.float32)
        m = mu * mom[k] + g
        new_m[k] = m
        new_p[k] = (p.astype(jnp.float32) - lr * m).astype(p.dtype)
    return new_p, new_m, new_stats, loss


def transformer_flops_per_step(batch, seq, layers, hidden, vocab):
    """Model FLOPs for one fused train step (fwd+bwd = 3x fwd matmuls).

    Matmul counting (dense 2mnk): qkv+out projections 4*D^2/tok/layer,
    FFN 8*D^2/tok/layer, vocab head D*V/tok; attention scores+values
    4*T*D/tok/layer counted over the FULL score matrix (both the ideal
    and the flash kernel do the causal work, so full-matrix counting is
    the consistent convention; halve for the causal-skip convention).
    """
    tokens = batch * seq
    proj = 2 * tokens * (layers * 12 * hidden * hidden + hidden * vocab)
    attn = 2 * tokens * layers * 2 * (2 * seq * hidden)
    return 3 * (proj + attn)


def _t_init(key, vocab, seq, layers, hidden, dtype=jnp.bfloat16):
    """GPT-2-small-geometry decoder LM params, bf16 weights + f32 norms."""
    rngs = iter(jax.random.split(key, 8 * layers + 8))
    p = {}

    def dense(name, fan_in, fan_out):
        p[name + "_w"] = (jax.random.normal(next(rngs), (fan_in, fan_out),
                                            jnp.float32)
                          * np.sqrt(1.0 / fan_in)).astype(dtype)
        p[name + "_b"] = jnp.zeros((fan_out,), dtype)

    def norm(name):
        p[name + "_g"] = jnp.ones((hidden,), jnp.float32)
        p[name + "_b"] = jnp.zeros((hidden,), jnp.float32)

    p["tok"] = (jax.random.normal(next(rngs), (vocab, hidden), jnp.float32)
                * 0.02).astype(dtype)
    p["pos"] = (jax.random.normal(next(rngs), (seq, hidden), jnp.float32)
                * 0.02).astype(dtype)
    for i in range(layers):
        pre = "l%d_" % i
        norm(pre + "ln1")
        dense(pre + "q", hidden, hidden)
        dense(pre + "k", hidden, hidden)
        dense(pre + "v", hidden, hidden)
        dense(pre + "proj", hidden, hidden)
        norm(pre + "ln2")
        dense(pre + "ff1", hidden, 4 * hidden)
        dense(pre + "ff2", 4 * hidden, hidden)
    norm("ln_f")
    dense("head", hidden, vocab)
    return p


def _t_forward(p, ids, layers, heads):
    """Pre-LN causal decoder matching models/transformer.py op-for-op."""
    hidden = p["tok"].shape[1]
    hd = hidden // heads

    def ln(name, x):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        y = (x32 - mu) * lax.rsqrt(var + 1e-5)
        return (y * p[name + "_g"] + p[name + "_b"]).astype(x.dtype)

    def dense(name, x):
        return x @ p[name + "_w"] + p[name + "_b"]

    x = p["tok"][ids] + p["pos"][None, :, :]
    B, T = ids.shape
    causal = jnp.tril(jnp.ones((T, T), jnp.bool_))
    for i in range(layers):
        pre = "l%d_" % i
        a = ln(pre + "ln1", x)
        q = dense(pre + "q", a).reshape(B, T, heads, hd)
        k = dense(pre + "k", a).reshape(B, T, heads, hd)
        v = dense(pre + "v", a).reshape(B, T, heads, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        scores = scores / np.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, hidden)
        x = x + dense(pre + "proj", att)
        f = ln(pre + "ln2", x)
        f = jax.nn.gelu(dense(pre + "ff1", f))
        x = x + dense(pre + "ff2", f)
    x = ln("ln_f", x)
    return dense("head", x).astype(jnp.float32)


def _chip_peaks(device_kind):
    """The one peaks table (analysis/costmodel.CHIP_PEAKS), loaded by path:
    importing the mxnet_tpu package would switch this process's jax to x64
    and 'highest' matmul precision, and the yardstick must stay plain jax."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "mxnet_tpu", "analysis", "costmodel.py")
    spec = importlib.util.spec_from_file_location("_costmodel_peaks", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.chip_peaks(device_kind)


def _transformer_main():
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    layers = int(os.environ.get("BENCH_LAYERS", "12"))
    hidden = int(os.environ.get("BENCH_HIDDEN", "768"))
    heads = int(os.environ.get("BENCH_HEADS", "12"))
    vocab = int(os.environ.get("BENCH_VOCAB", "32768"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    iters = int(os.environ.get("BENCH_ITERS", "30"))
    peak = _chip_peaks(jax.devices()[0].device_kind)["flops"]

    key = jax.random.PRNGKey(0)
    params = _t_init(key, vocab, seq, layers, hidden)
    mom = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    ids = jax.random.randint(key, (batch, seq), 0, vocab)
    labels = jax.random.randint(key, (batch, seq), 0, vocab)

    def loss_fn(p, ids, labels):
        logits = _t_forward(p, ids, layers, heads)
        logp = jax.nn.log_softmax(logits)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -jnp.mean(picked)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, mom, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn)(p, ids, labels)
        lr, mu = 1e-4, 0.9
        new_p, new_m = {}, {}
        for k, w in p.items():
            m = mu * mom[k] + grads[k].astype(jnp.float32)
            new_m[k] = m
            new_p[k] = (w.astype(jnp.float32) - lr * m).astype(w.dtype)
        return new_p, new_m, loss

    dump = os.environ.get("BENCH_DUMP_HLO")
    if dump:
        open(dump, "w").write(
            step.lower(params, mom, ids, labels).compile().as_text())

    for _ in range(warmup):
        params, mom, loss = step(params, mom, ids, labels)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, mom, loss = step(params, mom, ids, labels)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    tok_s = batch * seq * iters / dt
    mfu = transformer_flops_per_step(batch, seq, layers, hidden,
                                     vocab) * iters / dt / peak
    print(json.dumps({
        "metric": "transformer_ideal_tokens_per_sec",
        "value": round(tok_s, 2),
        "mfu": round(mfu, 4),
        "unit": "tokens/sec (L%d H%d T%d bs%d, bf16, pure-JAX)"
                % (layers, hidden, seq, batch)}))


def main():
    if os.environ.get("BENCH_MODEL", "resnet50") == "transformer":
        _transformer_main()
        return
    batch = int(os.environ.get("BENCH_BATCH", "32"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    iters = int(os.environ.get("BENCH_ITERS", "100"))
    key = jax.random.PRNGKey(0)
    params, stats = init_params(key)
    mom = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    # v2 parity: the framework feeds f32 and casts in-graph
    x_dtype = jnp.float32 if ARCH == "v2" else jnp.bfloat16
    x = jax.random.uniform(key, (batch, 224, 224, 3), x_dtype)
    labels = jax.random.randint(key, (batch,), 0, 1000)

    dump = os.environ.get("BENCH_DUMP_HLO")
    if dump:
        txt = train_step.lower(params, mom, stats, x, labels) \
            .compile().as_text()
        open(dump, "w").write(txt)

    for _ in range(warmup):
        params, mom, stats, loss = train_step(params, mom, stats, x, labels)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, mom, stats, loss = train_step(params, mom, stats, x, labels)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "resnet50_ideal_img_per_sec",
        "value": round(batch * iters / dt, 2),
        "unit": "images/sec (bs%d, bf16, pure-JAX NHWC, arch=%s)"
                % (batch, ARCH)}))


if __name__ == "__main__":
    main()
