#!/usr/bin/env python
"""On-chip microbenchmarks: Pallas kernels vs their XLA-naive
formulations (VERDICT r3 item 2 — a perf kernel needs a perf number).

Measures, on the real TPU:
  * fused_attention vs naive jnp attention (materialized (T,T) scores)
    at T in {1024, ..., 16384}, causal, bf16, ``--batch`` x ``--heads``
    x ``--dim`` (default 1 x 8 x 64) — forward only (``--mode=fwd``,
    default) or the full fwd+bwd training path (``--mode=fwdbwd``: the
    flash forward and backward kernels vs the ``_contrib_fused_attention``
    op's own einsum path (``flash_min_seq`` above T) vs XLA
    differentiating the naive float32 formulation, each with the bytes its
    compiled program plans; then, from a profiler trace, each ``flash_*``
    kernel's device time beside the least time its counted matrix work
    needs).  ``--mode=fwdbwd --seqs 1024 --batch 16 --heads 12 --no-reach``
    is the shape of the benchmark's ``gpt2s.train-b16`` cell.
  * two_bit_compress vs the two-pass XLA formulation on a 25M-element
    gradient (ResNet-50 scale; fwd mode only).

``--autotune`` first runs the measure-and-cache block-size search
(ops/autotune.py, forced on) for every benched shape, so the table and
the persisted cache come from the same run.

Prints one JSON line per measurement.  Timing: warmup, then a timed
chain of `iters` calls ended by block_until_ready.
"""
import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops, peaks  # noqa: E402


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


def _op_train_fn(flash_min_seq):
    """value_and_grad over the registered op with its dispatch pinned:
    ``flash_min_seq`` above T is the einsum path a short sequence takes."""
    from mxnet_tpu.ops.registry import get_op
    op = get_op("_contrib_fused_attention")
    attrs = op.parse_attrs(dict(causal=True, flash_min_seq=flash_min_seq))

    def loss(q, k, v):
        return jnp.sum(op.fn(attrs, q, k, v).astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2))


def planned_bytes(fn, args):
    """Arguments, results and temporaries of the compiled program: what
    it holds at its fullest, from the compiler's plan."""
    m = jax.jit(fn).lower(*args).compile().memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


# The matrix products the algorithm needs of each kernel, out of the six
# that benchmark/lib/flops.py::flash_train_flops_bytes counts for a layer
# (2 FLOPs a multiply-add over the causal T(T+1)/2 pairs): the forward's two
# and the backward's four (dP, dQ; dV, dK); the scores a backward kernel
# recomputes are not counted.
COUNTED_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2,
                    "flash_bwd": 4}


def least_seconds(kernel, B, T, H, D, peak):
    layer, _bytes = flops.flash_train_flops_bytes(
        {"n_embd": H * D, "n_layer": 1}, B, T)
    return COUNTED_PRODUCTS[kernel] * layer // 6 / peak


def kernel_seconds(fn, args, iters=10):
    """Device seconds a call of each ``flash_*`` kernel, from a profiler
    trace of ``iters`` calls (the trace's ``XLA Ops`` line names a Pallas
    kernel by its ``name=``)."""
    from jax.profiler import ProfileData
    jax.block_until_ready(fn(*args))
    totals = {}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        out = None
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        for plane in ProfileData.from_file(sorted(files)[-1]).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    # ``%jvp_flash_fwd_.3 = ...``: the call's name with the
                    # transforms round it; the longest kernel name it holds
                    name = ev.name.split(" = ", 1)[0]
                    kernel = max((k for k in COUNTED_PRODUCTS if k in name),
                                 key=len, default=None)
                    if kernel:
                        totals[kernel] = totals.get(kernel, 0.0) \
                            + ev.duration_ns * 1e-9
    return {k: v / iters for k, v in totals.items()}


def report_kernels(fn, args, B, T, H, D):
    """One line: each flash kernel's device milliseconds a call, and its
    share of the least time its counted products need on this chip."""
    peak = peaks.CHIP_PEAKS.get(jax.devices()[0].device_kind,
                                {}).get("flops")
    row = {"metric": "flash_kernel_ms", "T": T, "B": B, "H": H, "D": D}
    for kernel, seconds in sorted(kernel_seconds(fn, args).items()):
        row[kernel] = {"ms": round(seconds * 1e3, 4)}
        if peak and kernel in COUNTED_PRODUCTS:
            least = least_seconds(kernel, B, T, H, D, peak)
            row[kernel].update(least_ms=round(least * 1e3, 4),
                               share_pct=round(100 * least / seconds, 2))
    print(json.dumps(row))


def fwdbwd_row(qkv, scale):
    """Forward + backward of one layer three ways: the flash kernels, the
    op's own einsum path, XLA on the naive float32 formulation; each with
    its milliseconds and the bytes its compiled program plans."""
    B, T, H, D = qkv[0].shape
    row = {"metric": "attention_fwdbwd_ms", "T": T, "B": B, "H": H, "D": D}
    for name, fn in (("pallas", _flash_train_fn(True)),
                     ("op_einsum", _op_train_fn(T + 1)),
                     ("xla_naive", _naive_train_fn(scale))):
        try:
            row[name] = round(timed(jax.jit(fn), qkv) * 1e3, 3)
            row[name + "_planned_bytes"] = planned_bytes(fn, qkv)
        except Exception as e:       # the naive program runs out of HBM
            row[name] = "FAILS (%s)" % type(e).__name__
    if isinstance(row["xla_naive"], float):
        row["speedup"] = round(row["xla_naive"] / row["pallas"], 2)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=["fwd", "fwdbwd"], default="fwd")
    ap.add_argument("--autotune", action="store_true",
                    help="run the block-size search first (forced on) "
                         "and persist the cache")
    ap.add_argument("--seqs", default="1024,2048,4096,8192,16384")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64, help="head dimension")
    ap.add_argument("--no-reach", action="store_true",
                    help="skip the T=32768 reach probe (interpret-mode "
                         "smoke runs)")
    args = ap.parse_args(argv)
    from mxnet_tpu.ops import autotune as autotune_mod
    from mxnet_tpu.ops.pallas_kernels import (fused_attention,
                                              two_bit_compress)
    key = jax.random.PRNGKey(0)
    B, H, D = args.batch, args.heads, args.dim
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
            print(json.dumps({
                "metric": "attention_ms", "T": T,
                "pallas": round(t_pallas * 1e3, 3),
                "xla_naive": round(t_naive * 1e3, 3),
                "speedup": round(t_naive / t_pallas, 2)}))
        else:
            report_kernels(jax.jit(_flash_train_fn(True)), (q, k, v),
                           B, T, H, D)
            print(json.dumps(fwdbwd_row((q, k, v), scale)))
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
