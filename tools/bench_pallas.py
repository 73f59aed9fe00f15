#!/usr/bin/env python
"""On-chip microbenchmarks: Pallas kernels vs their XLA-naive
formulations (VERDICT r3 item 2 — a perf kernel needs a perf number).

Measures, on the real TPU:
  * fused_attention vs naive jnp attention (materialized (T,T) scores)
    at T in {1024, ..., 16384}, causal, bf16, B=1 H=8 D=64 — forward
    only (``--mode=fwd``, default) or the full fwd+bwd training path
    (``--mode=fwdbwd``: Pallas flash forward + the r6 recompute-free
    flash backward vs XLA differentiating the naive formulation).
  * two_bit_compress vs the two-pass XLA formulation on a 25M-element
    gradient (ResNet-50 scale; fwd mode only).

``--autotune`` first runs the measure-and-cache block-size search
(ops/autotune.py, forced on) for every benched shape, so the table and
the persisted cache come from the same run.

Prints one JSON line per measurement.  Timing: warmup, then a timed
chain of `iters` calls ended by block_until_ready (the bench.py
methodology).
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax
import jax.numpy as jnp
import numpy as np


def timed(fn, args, iters=50, warmup=5):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def naive_attention(q, k, v, scale):
    """The XLA formulation a user would write: full (T,T) scores."""
    B, T, H, D = q.shape
    qf = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kf = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vf = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


def two_pass_two_bit(grad, residual, threshold):
    comp = grad + residual
    q = jnp.where(comp >= threshold, threshold,
                  jnp.where(comp <= -threshold, -threshold, 0.0))
    return q.astype(grad.dtype), (comp - q).astype(grad.dtype)


def _flash_train_fn(causal=True):
    """value_and_grad over the Pallas flash custom vjp — the exact
    fwd+bwd pair the fused_attention op runs above MXNET_FLASH_MIN_SEQ."""
    from mxnet_tpu.ops.pallas_kernels import (fused_attention,
                                              fused_attention_bwd,
                                              fused_attention_fwd)

    @jax.custom_vjp
    def attn(q, k, v):
        return fused_attention(q, k, v, causal=causal)

    def fwd(q, k, v):
        out, lse = fused_attention_fwd(q, k, v, causal=causal)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, out, lse = res
        return fused_attention_bwd(q, k, v, out, lse, g, causal=causal)

    attn.defvjp(fwd, bwd)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2))


def _naive_train_fn(scale):
    def loss(q, k, v):
        return jnp.sum(naive_attention(q, k, v, scale).astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=["fwd", "fwdbwd"], default="fwd")
    ap.add_argument("--autotune", action="store_true",
                    help="run the block-size search first (forced on) "
                         "and persist the cache")
    ap.add_argument("--seqs", default="1024,2048,4096,8192,16384")
    ap.add_argument("--no-reach", action="store_true",
                    help="skip the T=32768 reach probe (interpret-mode "
                         "smoke runs)")
    args = ap.parse_args(argv)
    from mxnet_tpu.ops import autotune as autotune_mod
    from mxnet_tpu.ops.pallas_kernels import (fused_attention,
                                              two_bit_compress)
    key = jax.random.PRNGKey(0)
    B, H, D = 1, 8, 64
    scale = 1.0 / float(np.sqrt(D))
    seqs = [int(t) for t in args.seqs.split(",") if t]
    for T in seqs:
        q = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
        k = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
        v = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
        if args.autotune:
            tuned = autotune_mod.tune_flash(
                q, k, v, causal=True, force=True,
                kinds=("fwd", "bwd") if args.mode == "fwdbwd"
                else ("fwd",))
            print(json.dumps({"metric": "autotune", "T": T,
                              "blocks": {k2: list(v2) for k2, v2
                                         in tuned.items()}}))
        if args.mode == "fwd":
            t_pallas = timed(jax.jit(functools.partial(
                fused_attention, causal=True)), (q, k, v))
            t_naive = timed(jax.jit(functools.partial(
                naive_attention, scale=scale)), (q, k, v))
            name = "attention_ms"
        else:
            t_pallas = timed(jax.jit(_flash_train_fn(True)), (q, k, v))
            try:
                t_naive = timed(jax.jit(_naive_train_fn(scale)), (q, k, v))
            except Exception as e:
                print(json.dumps({
                    "metric": "attention_fwdbwd_ms", "T": T,
                    "pallas": round(t_pallas * 1e3, 3),
                    "xla_naive": "FAILS (%s)" % type(e).__name__}))
                continue
            name = "attention_fwdbwd_ms"
        print(json.dumps({
            "metric": name, "T": T,
            "pallas": round(t_pallas * 1e3, 3),
            "xla_naive": round(t_naive * 1e3, 3),
            "speedup": round(t_naive / t_pallas, 2)}))
    # reach probe: the flash kernel is HBM-bound, the naive program
    # needs the full (T, T) scores (and, in fwdbwd mode, their grads)
    if args.no_reach:
        return
    T = 32768
    q = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    reach_fn = jax.jit(functools.partial(fused_attention, causal=True)) \
        if args.mode == "fwd" else jax.jit(_flash_train_fn(True))
    t_pallas = timed(reach_fn, (q, q, q), iters=10)
    naive_fn = jax.jit(functools.partial(naive_attention, scale=scale)) \
        if args.mode == "fwd" else jax.jit(_naive_train_fn(scale))
    try:
        t_naive = round(timed(naive_fn, (q, q, q), iters=10) * 1e3, 3)
    except Exception as e:
        t_naive = "FAILS (%s)" % type(e).__name__
    print(json.dumps({"metric": "attention_ms" if args.mode == "fwd"
                      else "attention_fwdbwd_ms", "T": T,
                      "pallas": round(t_pallas * 1e3, 3),
                      "xla_naive": t_naive}))

    if args.mode == "fwd":
        n = 25_600_000
        g = jax.random.normal(key, (n,), jnp.float32)
        r = jnp.zeros((n,), jnp.float32)
        t_pallas = timed(jax.jit(lambda g, r: two_bit_compress(
            g, r, 0.5, use_pallas=True)), (g, r))
        t_xla = timed(jax.jit(lambda g, r: two_pass_two_bit(g, r, 0.5)),
                      (g, r))
        print(json.dumps({
            "metric": "two_bit_compress_ms", "elements": n,
            "pallas": round(t_pallas * 1e3, 3),
            "xla_two_pass": round(t_xla * 1e3, 3),
            "speedup": round(t_xla / t_pallas, 2)}))


if __name__ == "__main__":
    main()
