#!/usr/bin/env python
"""Framework-vs-ideal transformer benchmark sweep (PERF.md evidence).

For each sequence length, runs the framework train step (bench.py's
exact program) and the hand-written pure-JAX ideal
(tools/bench_ideal.py geometry: 12L/768H/12 heads) with one warmup
then WINDOWS timed chains of ITERS fused steps, reporting
mean +/- sigma tokens/sec and MFU against the published bf16 peak of the
device it ran on (analysis.costmodel.chip_peaks; a device with no entry
there, the CPU included, is an error).  Tokens per batch are held at 8192
across T so memory stays flat (bs = 8192 / T).

Usage: python tools/bench_transformer_sweep.py [T ...]   (default 1024 2048 4096)
Emits one JSON line per (program, T), each naming its device; exits
non-zero if any (program, T) failed.
"""
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

LAYERS, HIDDEN, HEADS, VOCAB = 12, 768, 12, 32768
TOKENS = int(os.environ.get("BENCH_TOKENS", "8192"))
ITERS = int(os.environ.get("BENCH_ITERS", "20"))
WINDOWS = int(os.environ.get("BENCH_WINDOWS", "5"))


def timed_windows(step_once):
    """One warmup sync, then WINDOWS chains of ITERS steps, each synced."""
    step_once()            # warmup/compile
    spans = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            step_once()
        step_once.sync()
        spans.append(time.perf_counter() - t0)
    return spans


def report(tag, seq, batch, spans, flops_per_step, phases=None):
    import jax
    from mxnet_tpu.analysis.costmodel import chip_peaks
    from mxnet_tpu.context import device_summary
    dev = jax.devices()[0]
    peak = chip_peaks(dev.device_kind)["flops"]
    toks = [batch * seq * ITERS / s for s in spans]
    mfus = [flops_per_step * ITERS / s / peak for s in spans]
    doc = {
        "program": tag, "seq": seq, "batch": batch,
        "device": device_summary([dev]),
        "tokens_per_sec_mean": round(statistics.mean(toks), 1),
        "tokens_per_sec_std": round(statistics.stdev(toks), 1),
        "mfu_mean": round(statistics.mean(mfus), 4),
        "mfu_std": round(statistics.stdev(mfus), 4),
        "windows": WINDOWS, "iters_per_window": ITERS,
    }
    if phases:
        doc["phases"] = phases
    print(json.dumps(doc), flush=True)


def attribution_phases(step, measured_step_s):
    """bench.py's phases block, reused here (satellite: every sweep line
    is self-describing).  ``step`` must be an AOT Compiled (the
    framework path); returns None for plain jitted callables."""
    if not hasattr(step, "as_text"):
        return None
    from mxnet_tpu.telemetry import perf as _perf
    rep = _perf.attribute_compiled(step, "sweep.framework",
                                   measured_step_s=measured_step_s)
    return _perf.phases_block(rep)


def run_framework(seq, batch):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.models.transformer import get_symbol
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    sym = get_symbol(vocab_size=VOCAB, seq_len=seq, num_layers=LAYERS,
                     hidden=HIDDEN, heads=HEADS)
    spec = MeshSpec(make_mesh((1,), ("dp",)))
    trainer = ShardedTrainer(sym, spec, lr=1e-4, momentum=0.9, wd=0.0,
                             param_dtype="bfloat16")
    shapes = {"data": (batch, seq), "softmax_label": (batch, seq)}
    params, mom, aux = trainer.init_state(shapes)
    step, params, mom, aux = trainer.build_step_auto_layout(
        params, mom, aux, shapes)
    keys = trainer._keys()
    key = jax.random.PRNGKey(0)
    data = jax.device_put(
        jax.random.randint(key, (batch, seq), 0, VOCAB).astype(jnp.float32),
        spec.batch_sharding())
    label = jax.device_put(
        jax.random.randint(key, (batch, seq), 0, VOCAB).astype(jnp.float32),
        spec.batch_sharding())
    feed = {"data": data, "softmax_label": label}
    state = [params, mom, aux, None, trainer._guard_arrays()]

    def step_once():
        state[0], state[1], state[2], state[3], _ok, state[4] = step(
            state[0], state[1], state[2], feed, keys, state[4])
    step_once.sync = lambda: jax.block_until_ready(state[3])
    spans = timed_windows(step_once)
    phases = attribution_phases(
        step, statistics.mean(spans) / ITERS)
    return spans, phases


def run_ideal(seq, batch):
    import jax
    import jax.numpy as jnp
    import importlib.util
    here = os.path.dirname(os.path.abspath(__file__))
    spec_ = importlib.util.spec_from_file_location(
        "bench_ideal", os.path.join(here, "bench_ideal.py"))
    bi = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(bi)

    key = jax.random.PRNGKey(0)
    params = bi._t_init(key, VOCAB, seq, LAYERS, HIDDEN)
    mom = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    ids = jax.random.randint(key, (batch, seq), 0, VOCAB)
    labels = jax.random.randint(key, (batch, seq), 0, VOCAB)

    def loss_fn(p, ids, labels):
        logits = bi._t_forward(p, ids, LAYERS, HEADS)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                             axis=-1))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, mom, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn)(p, ids, labels)
        new_p, new_m = {}, {}
        for k, w in p.items():
            m = 0.9 * mom[k] + grads[k].astype(jnp.float32)
            new_m[k] = m
            new_p[k] = (w.astype(jnp.float32) - 1e-4 * m).astype(w.dtype)
        return new_p, new_m, loss

    state = [params, mom, None]

    def step_once():
        state[0], state[1], state[2] = step(state[0], state[1], ids, labels)
    step_once.sync = lambda: jax.block_until_ready(state[2])
    return timed_windows(step_once)


def _one(program, seq):
    import importlib.util
    here = os.path.dirname(os.path.abspath(__file__))
    spec_ = importlib.util.spec_from_file_location(
        "bench_ideal_f", os.path.join(here, "bench_ideal.py"))
    bi = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(bi)
    batch = max(1, TOKENS // seq)
    flops = bi.transformer_flops_per_step(batch, seq, LAYERS, HIDDEN, VOCAB)
    runner = run_framework if program == "framework" else run_ideal
    result = runner(seq, batch)
    spans, phases = result if isinstance(result, tuple) else (result, None)
    report(program, seq, batch, spans, flops, phases=phases)


def main():
    # each (program, T) in its own subprocess: HBM must start empty for
    # every measurement (residue from the previous program OOMs T>=1k)
    import subprocess
    if len(sys.argv) >= 4 and sys.argv[1] == "--one":
        _one(sys.argv[2], int(sys.argv[3]))
        return
    seqs = [int(a) for a in sys.argv[1:]] or [1024, 2048, 4096]
    me = os.path.abspath(__file__)
    failed = []
    for seq in seqs:
        for program in ("framework", "ideal"):
            # the child's errors go straight to this process's stderr
            r = subprocess.run([sys.executable, me, "--one", program,
                                str(seq)], text=True,
                               stdout=subprocess.PIPE)
            sys.stdout.write(r.stdout)
            sys.stdout.flush()
            if r.returncode != 0:
                failed.append((program, seq, r.returncode))
    if failed:
        # the sweep goes on past a failure (T=32k not fitting is a
        # result), but it does not end in success
        sys.exit("bench_transformer_sweep: failed (program, T, exit code): "
                 "%s" % failed)


if __name__ == "__main__":
    main()
