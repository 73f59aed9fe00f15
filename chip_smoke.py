#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main paths once, through the entry points a user would call, at
the full width of the models the benchmark measures (depth is what it is there;
the weights are random, made from a seed):

  trainer/resnet50     models.resnet -> ShardedTrainer -> build_step_auto_layout
                       (the benchmark's path), 32 images per chip, batches put
                       from host memory every step
  trainer/transformer  models.transformer L12/H768/12 heads/V32768/T1024,
                       8 sequences per chip, bf16, contrib.fused_attention on
                       its default dispatch (Pallas flash forward + backward),
                       held against the einsum formulation of the same graph
  server/decode        DecodeProgram + DecodeEngine at the same width
                       (context 1024, page 16, 8 slots): 8 requests through
                       submit(), greedy tokens held against the XLA
                       attention formulation
  api/module_fit       example/image_classification/train_imagenet.py
                       --benchmark 1 (Module.fit on mx.tpu())

It names the device first, prints one line of evidence per phase (steps,
losses, tokens, where the arrays live, programs compiled) and no rate, and
ends its standard output with one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It exits non-zero — and prints no such line — when jax finds no TPU, and
where the repo is not beside it.  Everything runs in this one process, which
holds the chip; the device count comes from jax.devices(), so the same file
serves one chip and a four-chip host (there the trainers run over a dp mesh
with the ZeRO update, and a cut-depth pair checks dp-N against dp-1).
It reads nothing outside the checkout and needs no network and no git.
"""
import json
import os
import re
import sys
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# the width every phase runs at: GPT-2 small's, with a 32k vocabulary
VOCAB, HIDDEN, HEADS, LAYERS, SEQ = 32768, 768, 12, 12, 1024
IMAGES_PER_CHIP, SEQS_PER_CHIP = 32, 8
STEPS = 5
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


class Compiles:
    """Counts, from jax's own monitoring events, the programs this process
    had to obtain an executable for — and how many of those came out of the
    persistent compilation cache instead of the compiler."""

    def __init__(self):
        import jax.monitoring
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def since(self, mark):
        return (self.programs - mark[0], self.cache_hits - mark[1])

    def mark(self):
        return (self.programs, self.cache_hits)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def mosaic_kernels(hlo_text):
    """Instruction names of the Mosaic (compiled Pallas) custom calls in a
    compiled program's text; a kernel's pallas_call ``name=`` is in it."""
    return re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"',
                      hlo_text)


def check_no_interpreter(hlo_text, what):
    """An interpreted Pallas kernel reaches the host through a callback
    custom call; a program for the chip must hold none."""
    hits = re.findall(r'custom_call_target="([^"]*callback[^"]*)"', hlo_text)
    check(not hits, "%s holds host callbacks %s: a kernel was interpreted"
          % (what, sorted(set(hits))))


def where(arrays):
    """'tpu x N': the platforms and the number of distinct devices that hold
    shards of ``arrays``."""
    devs = {d for a in arrays for d in a.devices()}
    return "%s x %d" % ("+".join(sorted({d.platform for d in devs})),
                        len(devs)), devs


# ---------------------------------------------------------------------------
# trainers
# ---------------------------------------------------------------------------

def run_trainer(compiles, sym, devices, batches, lr, wd, steps,
                want_kernels=(), dtype="bfloat16"):
    """The benchmark's training path over a dp mesh of ``devices``: init, AOT
    step with compiler-chosen layouts, ``steps`` steps with every batch put
    from host memory, and the cross-entropy of the first batch (by the
    executor's own forward, over the same mesh) before and after.  Returns
    the evidence as a dict."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import audit
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    n = len(devices)
    spec = MeshSpec(make_mesh((n,), ("dp",), devices=devices))
    trainer = ShardedTrainer(
        sym, spec, lr=lr, momentum=0.9, wd=wd, zero=True,
        param_dtype=dtype if dtype != "float32" else None)
    shapes = {k: v.shape for k, v in batches[0].items()}
    params, mom, aux = trainer.init_state(shapes)

    rep, bat = spec.replicated(), spec.batch_sharding()
    prog = trainer.prog

    def cross_entropy(params, aux, inputs, keys):
        args = [None] * len(prog.arg_names)
        for i, p in zip(trainer.param_idx, params):
            args[i] = p
        for name, v in inputs.items():
            args[trainer.input_idx[name]] = v
        outs, _ = prog.evaluate(args, aux, keys, True)
        probs = outs[0].astype(jnp.float32)
        label = inputs["softmax_label"].reshape(-1, 1).astype(jnp.int32)
        picked = jnp.take_along_axis(
            probs.reshape(label.shape[0], -1), label, axis=1)
        return -jnp.mean(jnp.log(jnp.maximum(picked, 1e-30)))

    ce_fn = jax.jit(cross_entropy, in_shardings=(rep, rep, bat, rep),
                    out_shardings=rep)

    def ce_of(params, aux):
        # through host copies: the step keeps its state in layouts the
        # compiler chose, which are not this program's
        host = jax.tree_util.tree_map(np.asarray, (params, aux))
        with trainer._tracing_on_mesh():
            return float(ce_fn(host[0], host[1], batches[0],
                               trainer._keys()))

    ce = [ce_of(params, aux)]
    step, params, mom, aux = trainer.build_step_auto_layout(
        params, mom, aux, shapes)
    keys, guard = trainer._keys(), trainer._guard_arrays()
    after_first = None
    for i in range(steps):
        inputs = {k: jax.device_put(v, bat)
                  for k, v in batches[i % len(batches)].items()}
        params, mom, aux, loss, ok, guard = step(params, mom, aux, inputs,
                                                 keys, guard)
        jax.block_until_ready(loss)
        check(bool(ok), "step %d: the non-finite guard tripped" % i)
        if after_first is None:
            after_first = compiles.mark()
            first_mom = [np.asarray(m) for m in mom]
    ce.append(ce_of(params, aux))
    recompiles = compiles.since(after_first)[0]

    check(all(np.isfinite(ce)), "cross-entropy not finite: %s" % ce)
    check(ce[0] != ce[-1], "cross-entropy did not move: %s" % ce)
    # neither the later steps nor the second loss evaluation
    check(recompiles == 0, "%d programs compiled after the first step"
          % recompiles)
    place, devs = where(list(params) + list(mom) + list(aux))
    check(devs == set(devices) and all(d.platform == "tpu" for d in devs),
          "state lives on %s, wanted %s" % (sorted(map(str, devs)),
                                            list(map(str, devices))))
    mom_total = sum(m.nbytes for m in mom)
    mom_dev0 = sum(s.data.nbytes for m in mom
                   for s in m.addressable_shards if s.device == devices[0])
    check(mom_dev0 <= 1.1 * mom_total / n + 1e6,
          "optimizer state not 1/%d per device: %.1f of %.1f MB"
          % (n, mom_dev0 / 1e6, mom_total / 1e6))

    text = step.as_text()
    check_no_interpreter(text, "the train step")
    kernels = mosaic_kernels(text)
    for k in want_kernels:
        check(any(k in name for name in kernels),
              "no Mosaic custom call for %s in the step (found %s)"
              % (k, sorted(set(kernels))))
    acct = audit.collective_accounting(text, mesh=spec.mesh) if n > 1 else {}
    if n > 1:
        check(acct.get("all-gather", {}).get("count"),
              "ZeRO step holds no weight all-gather: %s" % sorted(acct))
    return {
        "ce": ce, "steps": steps, "place": place, "first_mom": first_mom,
        "param_names": trainer.param_names, "kernels": kernels,
        "mom_mb_per_device": mom_dev0 / 1e6, "mom_mb": mom_total / 1e6,
        "collectives": {k: v["count"] for k, v in sorted(acct.items())},
    }


def describe(ev):
    s = ("%d steps, cross-entropy %.4f -> %.4f, state on %s, optimizer "
         "state %.1f of %.1f MB on device 0, 0 programs compiled after "
         "step 1" % (ev["steps"], ev["ce"][0], ev["ce"][-1], ev["place"],
                     ev["mom_mb_per_device"], ev["mom_mb"]))
    if ev["collectives"]:
        s += ", collectives %s" % ev["collectives"]
    return s


def image_batches(rs, n, side=224, classes=1000, count=2):
    return [{"data": rs.rand(n, 3, side, side).astype(np.float32),
             "softmax_label": rs.randint(0, classes, n).astype(np.float32)}
            for _ in range(count)]


def token_batches(rs, n, count=2):
    return [{"data": rs.randint(0, VOCAB, (n, SEQ)).astype(np.float32),
             "softmax_label": rs.randint(0, VOCAB, (n, SEQ))
             .astype(np.float32)} for _ in range(count)]


def phase_resnet(compiles, devices):
    from mxnet_tpu.models import resnet
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape="3,224,224", dtype="bfloat16")
    batches = image_batches(np.random.RandomState(0),
                            IMAGES_PER_CHIP * len(devices))
    ev = run_trainer(compiles, sym, devices, batches, lr=0.1, wd=1e-4,
                     steps=STEPS)
    return describe(ev)


def grads_agree(a, b, what, tol_all, tol_one):
    """First-step gradients of two runs from the same init, read back from
    the f32 momentum (zero before the step, so it is -lr * grad): the
    relative error of the whole gradient, and the worst single tensor's.
    A tensor whose true gradient is zero (a key bias: softmax does not see
    it) is all rounding noise, so each tensor's error is taken against its
    own norm plus a hundredth of the whole gradient's."""
    check(a["param_names"] == b["param_names"], "parameter lists differ")
    err = [float(np.linalg.norm(x.astype(np.float64) - y))
           for x, y in zip(a["first_mom"], b["first_mom"])]
    ref = [float(np.linalg.norm(y.astype(np.float64)))
           for y in b["first_mom"]]
    ref_all = float(np.linalg.norm(ref))
    whole = float(np.linalg.norm(err)) / max(ref_all, 1e-30)
    worst = max((e / (r + 1e-2 * ref_all), n)
                for e, r, n in zip(err, ref, a["param_names"]))
    check(whole <= tol_all and worst[0] <= tol_one,
          "%s: first-step gradients differ by %.3g overall (allowed %.3g), "
          "%.3g in %s (allowed %.3g)"
          % (what, whole, tol_all, worst[0], worst[1], tol_one))
    return "gradients within %.2g overall, %.2g at worst (%s)" % (
        whole, worst[0], worst[1])


def phase_transformer(compiles, devices):
    from mxnet_tpu.models import transformer
    n = len(devices)
    batches = token_batches(np.random.RandomState(1), SEQS_PER_CHIP * n)
    geometry = dict(vocab_size=VOCAB, seq_len=SEQ, num_layers=LAYERS,
                    hidden=HIDDEN, heads=HEADS)
    # the reference first, one step, and gone before the run of record
    einsum = run_trainer(
        compiles, transformer.get_symbol(flash_min_seq=SEQ + 1, **geometry),
        devices, batches, lr=1e-4, wd=0.0, steps=1)
    check(not any(k in name for name in einsum["kernels"]
                  for k in FLASH_KERNELS),
          "the einsum reference ran flash kernels: %s" % einsum["kernels"])
    flash = run_trainer(
        compiles, transformer.get_symbol(**geometry), devices, batches,
        lr=1e-4, wd=0.0, steps=STEPS, want_kernels=FLASH_KERNELS)
    d_ce = abs(flash["ce"][0] - einsum["ce"][0])
    check(d_ce <= 2e-2, "flash and einsum forward losses differ: %.5f vs %.5f"
          % (flash["ce"][0], einsum["ce"][0]))
    agree = grads_agree(flash, einsum, "flash vs einsum", 5e-2, 0.15)
    used = sorted({k for k in FLASH_KERNELS
                   for name in flash["kernels"] if k in name})
    return ("%s; Mosaic kernels %s x %d calls, no interpreter; against the "
            "einsum path: loss %.4f vs %.4f, %s"
            % (describe(flash), used, len(flash["kernels"]),
               flash["ce"][0], einsum["ce"][0], agree))


def phase_dp_parity(compiles, devices):
    """Several chips only: the same global batch on the dp-N mesh (ZeRO)
    and on one chip, two steps from the same init, at cut depth — the
    mechanism under test (sharded batch, global BatchNorm statistics,
    per-device flash calls, sharded update) does not depend on depth.
    The ResNet pair runs in float32: in bf16 a freshly initialised ResNet
    amplifies the rounding that a different batch split brings to about a
    fifth of its gradient (measured on the CPU mesh: 0.22 in bf16, 0.002
    in f32), which would leave nothing to compare."""
    from mxnet_tpu.models import resnet, transformer
    out = []
    cases = [
        ("resnet18-f32", resnet.get_symbol(
            num_classes=1000, num_layers=18, image_shape="3,224,224",
            dtype="float32"),
         image_batches(np.random.RandomState(2), IMAGES_PER_CHIP), 0.01, 1e-4,
         "float32"),
        ("transformer-L2", transformer.get_symbol(
            vocab_size=VOCAB, seq_len=SEQ, num_layers=2, hidden=HIDDEN,
            heads=HEADS),
         token_batches(np.random.RandomState(3), SEQS_PER_CHIP), 1e-4, 0.0,
         "bfloat16"),
    ]
    for name, sym, batches, lr, wd, dtype in cases:
        many = run_trainer(compiles, sym, devices, batches, lr, wd, steps=2,
                           dtype=dtype)
        one = run_trainer(compiles, sym, devices[:1], batches, lr, wd,
                          steps=2, dtype=dtype)
        for a, b in zip(many["ce"], one["ce"]):
            check(abs(a - b) <= 1e-2 * max(abs(b), 1.0),
                  "%s: dp%d loss %s vs one chip %s"
                  % (name, len(devices), many["ce"], one["ce"]))
        agree = grads_agree(many, one, "%s dp%d vs one chip"
                            % (name, len(devices)), 3e-2, 0.15)
        out.append("%s dp%d %s == one chip %s, %s" % (
            name, len(devices), ["%.4f" % c for c in many["ce"]],
            ["%.4f" % c for c in one["ce"]], agree))
    return "; ".join(out)


# ---------------------------------------------------------------------------
# decode server
# ---------------------------------------------------------------------------

def serve(prog, prompts, new_tokens):
    from mxnet_tpu.serving.decode import DecodeEngine
    eng = DecodeEngine(prog)
    try:
        reqs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        outs = [np.asarray(r.result(timeout=600)[0]) for r in reqs]
        return outs, eng.stats()
    finally:
        eng.close()


def phase_decode(compiles, devices):
    from mxnet_tpu.ops import autotune
    from mxnet_tpu.serving.decode import (DecodeConfig, DecodeProgram,
                                          init_decode_params)
    cfg = DecodeConfig(VOCAB, LAYERS, HIDDEN, HEADS, SEQ, page_size=16,
                       max_seqs=8)
    backend = autotune.decode_backend(cfg.max_seqs, HEADS, cfg.head_dim,
                                      cfg.page_size, "float32")
    check(backend == "pallas", "default decode-attention backend on this "
          "device is %r, not the Pallas kernel" % backend)
    weights = init_decode_params(cfg, seed=0)
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32)
               for n in rs.randint(64, 129, 8)]
    new_tokens = 32

    prog = DecodeProgram(weights, cfg, name="smoke")
    mark = compiles.mark()
    outs, stats = serve(prog, prompts, new_tokens)
    check(prog.trace_count == 1 and stats["decode"]["compiles"] == 1,
          "the decode step traced %d times" % prog.trace_count)
    check(all(len(o) == new_tokens for o in outs),
          "token counts %s, wanted %d each" % ([len(o) for o in outs],
                                               new_tokens))
    text = prog.lowered_step_text()
    check_no_interpreter(text, "the decode step")
    kernels = mosaic_kernels(text)
    budget = prog.config.prefill_tokens_per_step
    for kernel in ("decode_attn", "chunk_attn"):
        check(any(kernel in k for k in kernels),
              "no Mosaic custom call for %s (found %s)" % (kernel, kernels))
    check(budget == prog.derived_budget(cfg) > 0,
          "the step takes %s prompt rows, not the derived budget" % budget)
    place, devs = where(list(prog._params.values()))
    check(devs == {devices[0]} and devices[0].platform == "tpu",
          "weights live on %s" % sorted(map(str, devs)))
    programs = compiles.since(mark)[0]

    # the same requests through the XLA attention formulation
    os.environ["MXNET_TPU_PALLAS_DECODE"] = "0"
    try:
        ref_prog = DecodeProgram(weights, cfg, name="smoke-xla")
        ref_outs, _ = serve(ref_prog, prompts, new_tokens)
        check(not mosaic_kernels(ref_prog.lowered_step_text()),
              "the XLA reference ran a Mosaic kernel")
    finally:
        del os.environ["MXNET_TPU_PALLAS_DECODE"]
    wrong = [i for i, (a, b) in enumerate(zip(outs, ref_outs))
             if not np.array_equal(a, b)]
    check(not wrong, "greedy tokens differ from the XLA formulation in "
          "requests %s" % wrong)
    return ("%d requests, prompts %s tokens, %d new tokens each == the XLA "
            "formulation's one token a slot a step; %d engine steps of %d "
            "prompt rows (%d prefill + %d decode tokens), step traced once, "
            "Mosaic decode_attn x %d, chunk_attn x %d calls, no "
            "interpreter, weights on %s, %d programs compiled"
            % (len(prompts), [len(p) for p in prompts], new_tokens,
               stats["counters"]["steps"], budget,
               stats["decode"]["tokens_prefilled"],
               stats["decode"]["tokens_decoded"],
               sum("decode_attn" in k for k in kernels),
               sum("chunk_attn" in k for k in kernels), place, programs))


LATENT_MODEL = dict(
    hidden_size=512, num_attention_heads=8, qk_nope_head_dim=128,
    qk_rope_head_dim=64, kv_lora_rank=512, v_head_dim=128,
    intermediate_size=1024, moe_intermediate_size=256, num_experts=4,
    num_experts_per_tok=2, num_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(type="deepseek_yarn", factor=40, beta_fast=32,
                      beta_slow=1, mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=4096),
    router_width=8, first_expert=0)
LATENT_LAYERS, LATENT_VOCAB, LATENT_SEQ = 3, 2048, 1024


def latent_program(dtype, name):
    from mxnet_tpu.serving.decode import (DecodeConfig, LatentDecodeProgram,
                                          init_decode_params)
    m = LATENT_MODEL
    cfg = DecodeConfig(LATENT_VOCAB, LATENT_LAYERS, m["hidden_size"],
                       m["num_attention_heads"], LATENT_SEQ, page_size=64,
                       max_seqs=8, family="sarvam_mla", dtype=dtype,
                       prefill_tokens_per_step=64, model=m)
    return LatentDecodeProgram(init_decode_params(cfg, seed=0, scale=0.05),
                               cfg, name=name)


def one_mixed_step(prog):
    """Slot 0 decodes at position 99 over a cache of that step's own making,
    slot 1 takes 40 prompt rows from position 70: (logits, layer 0 of the
    pool) after a 100-row fill and that step."""
    c = prog.config
    S, page, R = c.max_seqs, c.page_size, prog.rows
    table = np.zeros((S, c.pages_per_seq), np.int32)
    table[0], table[1] = 1 + np.arange(16), 17 + np.arange(16)
    rs = np.random.RandomState(6)
    kv = prog.fresh_cache()
    out = None
    for first, n0, n1 in ((0, 0, 64), (64, 0, 6), (70, 1, 40)):
        tokens = np.zeros(R, np.int32)
        positions = np.full(R, -1, np.int32)
        row_slot = np.zeros(R, np.int32)
        row_slot[:S] = np.arange(S)
        seq_lens = np.zeros(S, np.int32)
        out_row = np.arange(S, dtype=np.int32)
        rows = S + np.arange(n1)
        positions[rows] = first + np.arange(n1)
        row_slot[S:] = 1
        seq_lens[1], out_row[1] = first + n1, rows[-1]
        if n0:
            positions[0], seq_lens[0] = 99, 100
        tokens[positions >= 0] = rs.randint(0, LATENT_VOCAB,
                                            int((positions >= 0).sum()))
        live = np.maximum(positions, 0)
        phys = np.where(positions >= 0, table[row_slot, live // page], 0)
        out = prog.step(kv, tokens, positions, seq_lens,
                        phys.astype(np.int32), (live % page).astype(np.int32),
                        table, None, row_slot, out_row)
        kv = out[2]
    return np.asarray(out[1], np.float32), np.asarray(kv[0], np.float32)


def phase_latent_decode(compiles, devices):
    """A small ``sarvam_mla`` program (latent rows of 576 in 640 lanes, 4 of
    8 experts held) through the engine: Mosaic ``mla_attn`` and
    ``latent_write`` once a layer, one trace, float32 tokens equal to the XLA
    formulation's; and one mixed step in bfloat16, the benchmark's dtype,
    close to it (greedy tokens of a bfloat16 model flip on rounding)."""
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, LATENT_VOCAB, n).astype(np.int32)
               for n in rs.randint(40, 300, 12)]
    new_tokens = 24
    prog = latent_program("float32", "smoke-mla")
    outs, stats = serve(prog, prompts, new_tokens)
    check(prog.trace_count == 1 and stats["decode"]["compiles"] == 1,
          "the many-token step traced %d times" % prog.trace_count)
    check(stats["decode"]["tokens_prefilled"] == sum(map(len, prompts))
          and stats["decode"]["tokens_decoded"] == 12 * new_tokens,
          "counted %s" % stats["decode"])
    text = prog.lowered_step_text()
    check_no_interpreter(text, "the many-token step")
    kernels = mosaic_kernels(text)
    for name in ("mla_attn", "latent_write"):
        n = sum(name in k for k in kernels)
        check(n == LATENT_LAYERS, "%d Mosaic calls of %s in a step of %d "
              "layers (found %s)" % (n, name, LATENT_LAYERS, kernels))
    place, devs = where(list(prog._params.values()))
    check(devs == {devices[0]} and devices[0].platform == "tpu",
          "weights live on %s" % sorted(map(str, devs)))
    low = one_mixed_step(latent_program("bfloat16", "smoke-mla-bf16"))
    os.environ["MXNET_TPU_PALLAS_DECODE"] = "0"
    try:
        ref_prog = latent_program("float32", "smoke-mla-xla")
        ref_outs, _ = serve(ref_prog, prompts, new_tokens)
        check(not any("mla_attn" in k or "latent_write" in k for k in
                      mosaic_kernels(ref_prog.lowered_step_text())),
              "the XLA reference ran a latent-pool kernel")
        ref_low = one_mixed_step(latent_program("bfloat16", "smoke-mla-bf16x"))
    finally:
        del os.environ["MXNET_TPU_PALLAS_DECODE"]
    wrong = [i for i, (a, b) in enumerate(zip(outs, ref_outs))
             if not np.array_equal(a, b)]
    check(not wrong, "float32 greedy tokens differ from the XLA formulation "
          "in requests %s" % wrong)
    gap = np.abs(low[0][:2] - ref_low[0][:2]).max() / np.abs(ref_low[0][:2]).max()
    check(gap < 0.03, "bfloat16 logits of the mixed step lie %.3f of their "
          "scale from the XLA formulation's" % gap)
    # layer 0's rows are made of the same numbers on both sides: all but the
    # trash page must be equal to the bit
    check(np.array_equal(low[1][1:], ref_low[1][1:]),
          "latent_write left other rows in layer 0 of the pool than the "
          "XLA scatter")
    return ("%d requests, prompts %s tokens, %d new tokens each == the XLA "
            "formulation's in float32; %d engine steps, step traced once, "
            "Mosaic mla_attn x %d and latent_write x %d, no interpreter; a "
            "mixed bfloat16 step within %.4f of its logits' scale, layer 0 "
            "of the pool equal to the bit; weights on %s"
            % (len(prompts), [len(p) for p in prompts], new_tokens,
               stats["counters"]["steps"],
               sum("mla_attn" in k for k in kernels),
               sum("latent_write" in k for k in kernels), gap, place))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def phase_module_fit(compiles, devices):
    import mxnet_tpu as mx
    sys.path.insert(0, os.path.join(HERE, "example", "image_classification"))
    import train_imagenet
    batch, n_batches = 32, 4
    mod = train_imagenet.main([
        "--benchmark", "1", "--network", "resnet", "--num-layers", "50",
        "--dtype", "bfloat16", "--kv-store", "tpu", "--batch-size",
        str(batch), "--num-epochs", "1", "--num-examples",
        str(batch * n_batches), "--disp-batches", "1000"])
    arrays = [a.handle for per_dev in mod._exec_group_param_arrays()
              for a in per_dev] + [o.handle for o in mod.get_outputs()]
    place, devs = where(arrays)
    check(devs == {devices[0]} and devices[0].platform == "tpu",
          "Module arrays live on %s" % sorted(map(str, devs)))
    # one more batch after fit: nothing is left to compile
    rs = np.random.RandomState(5)
    extra = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(batch, 3, 224, 224).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 1000, batch).astype(np.float32))])
    mark = compiles.mark()
    mod.forward_backward(extra)
    mod.update()
    probs = mod.get_outputs()[0].asnumpy().astype(np.float32)
    check(np.isfinite(probs).all() and probs.shape == (batch, 1000),
          "outputs %s finite=%s" % (probs.shape, np.isfinite(probs).all()))
    arg_params, _ = mod.get_params()
    check(all(np.isfinite(v.asnumpy().astype(np.float32)).all()
              for v in arg_params.values()), "non-finite parameters after fit")
    again = compiles.since(mark)[0]
    check(again == 0, "%d programs compiled by a batch after fit" % again)
    return ("Module.fit %d batches of %d + 1 by hand, outputs %s finite, "
            "%d parameter arrays and the outputs on %s, 0 programs compiled "
            "after fit" % (n_batches, batch, probs.shape,
                           len(arg_params), place))


def main():
    import jax
    from mxnet_tpu.context import device_summary  # and x64, cache placement

    devices = jax.devices()
    device = device_summary(devices)
    print("device platform=%(platform)s device_kind=%(kind)s count=%(count)d"
          % device, flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: jax found no TPU (jax.devices() = %s); nothing "
              "was run" % devices, file=sys.stderr)
        return 2
    print("jax %s, compilation cache at %s" % (
        jax.__version__, jax.config.jax_compilation_cache_dir), flush=True)

    compiles = Compiles()
    phases = [("trainer/resnet50", phase_resnet),
              ("trainer/transformer", phase_transformer),
              ("server/decode", phase_decode),
              ("server/latent-decode", phase_latent_decode),
              ("api/module_fit", phase_module_fit)]
    if len(devices) > 1:
        phases.insert(2, ("trainer/dp-parity", phase_dp_parity))
    failed = []
    for name, phase in phases:
        mark = compiles.mark()
        try:
            evidence = phase(compiles, devices)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print("FAIL %s" % name, flush=True)
            continue
        programs, hits = compiles.since(mark)
        print("PASS %s: %s [%d programs, %d from the persistent cache]"
              % (name, evidence, programs, hits), flush=True)
    print(json.dumps({"ok": not failed, "device": device,
                      **({"failed": failed} if failed else {})}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
