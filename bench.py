"""Benchmark of record: ResNet-50 training throughput, images/sec/chip.

Baseline (BASELINE.md): reference MXNet ResNet-50 train bs32 on K80 =
45.52 img/s (docs/faq/perf.md:146-180).  This benchmark runs the same
workload TPU-natively: one fused XLA train step (fwd+bwd+SGD update,
donated buffers) via parallel.ShardedTrainer, data resident in HBM,
bfloat16 activations/params with fp32 BN statistics (the TPU-native
precision recipe; set BENCH_DTYPE=float32 for strict fp32).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device"}.
"device" is what jax reports for the devices the number was taken on
(platform, device_kind, count).  A host without an accelerator is refused:
a CPU timing is not a device metric.  Any phase that fails, fails the run.

One process per chip: with BENCH_MODEL unset the artifact of record carries
ResNet-50 and the transformer, and this process — which never touches jax —
runs each in a child of its own, one after the other, so each starts on an
empty chip that nothing else holds.

BENCH_IO=1 switches to the end-to-end mode: batches come from a RecordIO
file through the native C++ decode pipeline (native/record_iter.cc), host
decode + host->device transfer overlapped with device compute — the analog
of the reference's train_imagenet.py with ImageRecordIter.  Payload crosses
the wire as uint8 NCHW (the TPU-native recipe: normalize on device, not on
host) and the train step casts on device.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_IMG_S = 45.52  # reference K80 bs32 (docs/faq/perf.md)


def _ensure_bench_rec(n_images, hw):
    """Synthesize (once) a RecordIO dataset of random JPEGs for BENCH_IO."""
    import io as pyio
    from PIL import Image
    from mxnet_tpu import recordio
    prefix = os.environ.get(
        "BENCH_REC_PREFIX",
        "/tmp/mxnet_tpu_bench_%dx%d_%d" % (hw, hw, n_images))
    if os.path.isfile(prefix + ".rec") and os.path.isfile(prefix + ".idx"):
        return prefix
    rs = np.random.RandomState(0)
    # write to temp names, rename when complete: an interrupted run must
    # not leave a truncated dataset that later runs silently reuse
    tmp = prefix + ".part"
    w = recordio.MXIndexedRecordIO(tmp + ".idx", tmp + ".rec", "w")
    for i in range(n_images):
        arr = rs.randint(0, 256, (hw, hw, 3), dtype=np.uint8)
        buf = pyio.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=90)
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 1000), i, 0), buf.getvalue()))
    w.close()
    os.rename(tmp + ".rec", prefix + ".rec")
    os.rename(tmp + ".idx", prefix + ".idx")
    return prefix


def _transformer_flops_per_step(batch, seq, layers, hidden, vocab):
    """One true FLOPs/MFU formula, loaded from tools/bench_ideal.py so
    framework and ideal MFU can never drift apart."""
    import importlib.util
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "bench_ideal_flops", os.path.join(here, "tools", "bench_ideal.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.transformer_flops_per_step(batch, seq, layers, hidden, vocab)


def _accelerators():
    """The devices this benchmark measures, as jax reports them."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit(
            "bench.py: jax found no accelerator (jax.devices() = %s); a "
            "timing taken on the CPU is not a device metric, so nothing "
            "is measured" % devices)
    return devices


def _attach_phases(result, step, n_dev, step_time_s, tag):
    """Attribution phases block: roofline shares + MFU + report path in
    the bench JSON line, so every BENCH_* artifact is self-describing
    (telemetry/perf.py; needs the AOT-compiled step — BENCH_AUTO_LAYOUT=0
    skips it), plus the total jit compile time this process paid, from the
    compile/ span family (an ungated ledger extra, like peak_hbm_bytes)."""
    from mxnet_tpu.telemetry import tracing as _tracing
    if hasattr(step, "as_text"):
        from mxnet_tpu.telemetry import perf as _perf
        rep = _perf.attribute_compiled(step, "bench.%s" % tag,
                                       n_devices=n_dev,
                                       measured_step_s=step_time_s)
        path = os.environ.get(
            "BENCH_ATTRIBUTION_PATH",
            "/tmp/mxnet_tpu_bench_attr_%s_%d.json" % (tag, os.getpid()))
        rep.save(path)
        result["phases"] = _perf.phases_block(rep, path)
    cs = _tracing.compile_summary()
    if cs["count"]:
        phases = result.setdefault("phases", {})
        phases["compile_seconds"] = cs["total_seconds"]
        phases["compile_by_name"] = cs["by_name"]


def _maybe_ledger(result):
    """BENCH_LEDGER=path: append this run to the benchwatch trajectory
    ledger at that path (tools/benchwatch.py gates it)."""
    path = os.environ.get("BENCH_LEDGER")
    if not path:
        return
    import importlib.util
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "benchwatch_feed", os.path.join(here, "tools", "benchwatch.py"))
    bw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bw)
    bw.append_entry(path, bw.extract_metrics(result), source="bench.py",
                    extra=bw.extract_extra(result) or None)


def _transformer_main():
    """BENCH_MODEL=transformer: decoder-only LM training tokens/sec —
    the attention-path number of record (GPT-2-small-ish geometry by
    default: 12 layers, 768 hidden, 12 heads, T=1024).  Reports MFU
    against the published bf16 peak of the device it ran on
    (analysis.costmodel.chip_peaks; a chip not in that table is an
    error)."""
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    seq_len = int(os.environ.get("BENCH_SEQ", "1024"))
    layers = int(os.environ.get("BENCH_LAYERS", "12"))
    hidden = int(os.environ.get("BENCH_HIDDEN", "768"))
    heads = int(os.environ.get("BENCH_HEADS", "12"))
    vocab = int(os.environ.get("BENCH_VOCAB", "32768"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    iters = int(os.environ.get("BENCH_ITERS", "30"))

    import jax
    import jax.numpy as jnp
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.context import device_summary
    from mxnet_tpu.analysis.costmodel import chip_peaks
    from mxnet_tpu.models.transformer import get_symbol
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    devices = _accelerators()
    n_dev = len(devices)
    peak = chip_peaks(devices[0].device_kind)["flops"]
    sym = get_symbol(vocab_size=vocab, seq_len=seq_len,
                     num_layers=layers, hidden=hidden, heads=heads)
    spec = MeshSpec(make_mesh((n_dev,), ("dp",)))
    trainer = ShardedTrainer(sym, spec, lr=1e-4, momentum=0.9, wd=0.0,
                             param_dtype=dtype if dtype != "float32" else None)
    gb = batch * n_dev
    shapes = {"data": (gb, seq_len), "softmax_label": (gb, seq_len)}
    params, mom, aux = trainer.init_state(shapes)
    if os.environ.get("BENCH_AUTO_LAYOUT", "1") != "0":
        step, params, mom, aux = trainer.build_step_auto_layout(
            params, mom, aux, shapes)
    else:
        from mxnet_tpu.parallel.trainer import sgd_step_fn
        step = sgd_step_fn(trainer)
    keys = trainer._keys()
    guard = trainer._guard_arrays()
    key = jax.random.PRNGKey(0)
    data = jax.device_put(
        jax.random.randint(key, (gb, seq_len), 0, vocab)
        .astype(jnp.float32), spec.batch_sharding())
    label = jax.device_put(
        jax.random.randint(key, (gb, seq_len), 0, vocab)
        .astype(jnp.float32), spec.batch_sharding())
    batch_dict = {"data": data, "softmax_label": label}
    for _ in range(warmup):
        params, mom, aux, loss, _ok, guard = step(params, mom, aux, batch_dict, keys, guard)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, mom, aux, loss, _ok, guard = step(params, mom, aux, batch_dict, keys, guard)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    tok_s = gb * seq_len * iters / dt / n_dev
    mfu = _transformer_flops_per_step(gb, seq_len, layers, hidden,
                                      vocab) * iters / dt / (peak * n_dev)
    result = {
        "metric": "transformer_train_tokens_per_sec_per_chip",
        "value": round(tok_s, 2),
        "mfu": round(mfu, 4),
        "unit": "tokens/sec/chip (L%d H%d T%d bs%d, %s)" % (
            layers, hidden, seq_len, batch, dtype),
        "vs_baseline": None,
        "device": device_summary(devices),
    }
    _attach_phases(result, step, n_dev, dt / iters, "transformer")
    return result


def _recommender_main():
    """BENCH_MODEL=recommender: DLRM-style criteo-toy click predictor —
    the sparse-at-scale number of record.  Categorical features hit
    mesh-sharded embedding tables through the routed lookup
    (mxnet_tpu/sparse: all-to-all bytes ~ touched rows, tables
    row-sharded over dp), dense features run the MLP, and the tables
    take the touched-rows-only lazy SGD.  Geometry knobs:
    BENCH_REC_TABLES/VOCAB/EMBED_DIM/DENSE, batch via BENCH_BATCH.
    MXNET_TPU_PALLAS_EMBED picks the shard-local kernel backend (unset:
    the autotune-cache winner)."""
    batch = int(os.environ.get("BENCH_BATCH", "4096"))
    n_tables = int(os.environ.get("BENCH_REC_TABLES", "4"))
    vocab = int(os.environ.get("BENCH_REC_VOCAB", "100000"))
    dim = int(os.environ.get("BENCH_REC_EMBED_DIM", "16"))
    dense_dim = int(os.environ.get("BENCH_REC_DENSE", "13"))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))

    import jax
    import jax.numpy as jnp
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.context import device_summary
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.sparse import (ShardedEmbedding, make_recommender_step,
                                  recommender_state,
                                  step_alltoall_model_bytes)

    devices = _accelerators()
    n_dev = len(devices)
    spec = MeshSpec(make_mesh((n_dev,), ("dp",)))
    gb = batch * n_dev
    embs = [ShardedEmbedding(vocab, dim, spec, name="table%d" % f)
            for f in range(n_tables)]
    state = recommender_state(embs, dense_dim=dense_dim,
                              hidden=(64, 32), seed=0)
    step = make_recommender_step(embs, lr=0.05, momentum=0.9)
    key = jax.random.PRNGKey(0)
    bat = spec.batch_sharding()
    from jax.sharding import NamedSharding, PartitionSpec as P
    ids = jax.device_put(
        jax.random.randint(key, (n_tables, gb), 0, vocab, jnp.int32),
        NamedSharding(spec.mesh, P(None, "dp")))
    dense = jax.device_put(
        jax.random.uniform(key, (gb, dense_dim), jnp.float32), bat)
    label = jax.device_put(
        (jax.random.uniform(key, (gb,)) > 0.5).astype(jnp.float32), bat)
    feed = {"ids": ids, "dense": dense, "label": label}
    for _ in range(warmup):
        state, loss = step(state, feed)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, feed)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    ex_s = gb * iters / dt / n_dev
    a2a = n_tables * step_alltoall_model_bytes(gb, dim, n_dev)
    result = {
        "metric": "recommender_train_examples_per_sec_per_chip",
        "value": round(ex_s, 2),
        "unit": "examples/sec/chip (%d tables x %dx%d, dense %d, bs%d)"
                % (n_tables, vocab, dim, dense_dim, batch),
        "vs_baseline": None,
        "device": device_summary(devices),
        "embedding": {
            "tables": n_tables, "vocab": vocab, "dim": dim,
            "table_mb_total": round(
                sum(e.table_bytes for e in embs) / 1e6, 2),
            "alltoall_model_bytes_per_step": a2a,
            "backend": embs[0].backend or "auto",
        },
    }
    _attach_phases(result, step, n_dev, dt / iters, "recommender")
    return result


def _decode_main():
    """BENCH_MODEL=decode: interactive decode steady-state — tokens/sec/
    chip of the paged-KV continuous-batching step (mxnet_tpu/serving/
    decode) with every slot occupied mid-sequence, the regime a loaded
    interactive fleet runs in.  Geometry knobs BENCH_DECODE_{LAYERS,
    HIDDEN,HEADS,VOCAB,SEQ,SLOTS,PAGE,QUANT}; MXNET_TPU_PALLAS_DECODE
    picks the attention backend.  The continuous-vs-static batching
    comparison lives in tools/servebench.py --decode."""
    layers = int(os.environ.get("BENCH_DECODE_LAYERS", "4"))
    hidden = int(os.environ.get("BENCH_DECODE_HIDDEN", "256"))
    heads = int(os.environ.get("BENCH_DECODE_HEADS", "8"))
    vocab = int(os.environ.get("BENCH_DECODE_VOCAB", "2048"))
    seq = int(os.environ.get("BENCH_DECODE_SEQ", "256"))
    slots = int(os.environ.get("BENCH_DECODE_SLOTS", "8"))
    page = int(os.environ.get("BENCH_DECODE_PAGE", "16"))
    quant = os.environ.get("BENCH_DECODE_QUANT") or None
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    iters = int(os.environ.get("BENCH_ITERS", "50"))

    import jax
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.context import device_summary
    from mxnet_tpu.analysis.costmodel import decode_step_model
    from mxnet_tpu.serving.decode import (DecodeConfig, DecodeProgram,
                                          init_decode_params)

    # the program is un-meshed: it serves from the default device alone
    devices = _accelerators()[:1]
    cfg = DecodeConfig(vocab, layers, hidden, heads, seq, page_size=page,
                       max_seqs=slots, quantize=quant)
    prog = DecodeProgram(init_decode_params(cfg, seed=0), cfg,
                         name="bench")
    prog.ensure_compiled()
    kv = prog.fresh_cache()
    pp = cfg.pages_per_seq
    table = np.zeros((slots, pp), np.int32)
    for s in range(slots):
        table[s] = 1 + s * pp + np.arange(pp)
    rs = np.random.RandomState(0)
    # steady state: every slot mid-sequence (half the context cached)
    base = seq // 2
    toks = rs.randint(0, vocab, slots).astype(np.int32)

    def one(kv, pos):
        positions = np.full(slots, pos, np.int32)
        nxt, _lg, kv = prog.step(
            kv, toks, positions, positions + 1,
            table[np.arange(slots), pos // page],
            np.full(slots, pos % page, np.int32), table)
        return nxt, kv
    pos = base
    for _ in range(warmup):
        nxt, kv = one(kv, pos)
        pos += 1
    jax.block_until_ready(nxt)
    t0 = time.perf_counter()
    for _ in range(iters):
        nxt, kv = one(kv, pos)
        pos += 1
    jax.block_until_ready(nxt)
    dt = time.perf_counter() - t0
    tok_s = slots * iters / dt
    model = decode_step_model(
        layers, hidden, vocab, slots, slots * base,
        quant_bits={"int8": 8, "int4": 4}.get(quant, 32))
    result = {
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(tok_s, 2),
        "unit": "tokens/sec/chip (L%d H%d heads%d V%d T%d S%d page%d%s)"
                % (layers, hidden, heads, vocab, seq, slots, page,
                   " %s" % quant if quant else ""),
        "vs_baseline": None,
        "device": device_summary(devices),
        "decode": {
            "step_ms": round(dt / iters * 1e3, 4),
            "cached_tokens": slots * base,
            "quantize": quant,
            "compiles": prog.trace_count,
            "model_hbm_bytes_per_step": int(model["hbm_bytes"]),
            "model_weight_bytes": int(model["weight_bytes"]),
        },
    }
    # the toy decode program's jit time deliberately does NOT ride the
    # phases block: phases.compile_seconds is the GATED trainer-compile
    # series, and a different program class would poison its trajectory
    from mxnet_tpu.telemetry import tracing as _tracing
    cs = _tracing.compile_summary()
    if cs["count"]:
        result["decode"]["compile_seconds"] = cs["total_seconds"]
    return result


def _run_child(model, drop=()):
    """One model in a process of its own; returns its result dict.  The
    child's failure is this run's failure, with the child's own words."""
    env = dict(os.environ, BENCH_MODEL=model)
    # the parent appends ONE ledger entry carrying both metrics
    for knob in ("BENCH_LEDGER",) + tuple(drop):
        env.pop(knob, None)
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       env=env, stdout=subprocess.PIPE, text=True,
                       timeout=1800)
    if r.returncode != 0:
        raise SystemExit("bench.py: the %s run failed (exit %d); its "
                         "errors are above" % (model, r.returncode))
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    model = os.environ.get("BENCH_MODEL")
    if model is None and os.environ.get("BENCH_IO", "0") != "1" \
            and os.environ.get("BENCH_TRANSFORMER", "1") != "0":
        # the artifact of record: ResNet-50 with the attention-path number
        # beside it.  The LM must not inherit ResNet geometry knobs or the
        # parent's attribution path (it has its own).
        result = _run_child("resnet50")
        result["transformer"] = _run_child(
            "transformer", drop=("BENCH_BATCH", "BENCH_ITERS",
                                 "BENCH_WARMUP", "BENCH_ATTRIBUTION_PATH"))
    else:
        runners = {"resnet50": _resnet_main,
                   "transformer": _transformer_main,
                   "decode": _decode_main,
                   "recommender": _recommender_main}
        model = model or "resnet50"
        if model not in runners:
            raise SystemExit("BENCH_MODEL must be one of %s, got %r"
                             % (sorted(runners), model))
        result = runners[model]()
    _maybe_ledger(result)
    print(json.dumps(result))


def _resnet_main():
    batch = int(os.environ.get("BENCH_BATCH", "32"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    iters = int(os.environ.get("BENCH_ITERS", "100"))
    layout = os.environ.get("BENCH_LAYOUT", "NCHW")  # NHWC: channels-last path
    if layout not in ("NCHW", "NHWC"):
        raise SystemExit("BENCH_LAYOUT must be NCHW or NHWC, got %r" % layout)
    if layout == "NHWC" and os.environ.get("BENCH_IO", "0") == "1":
        raise SystemExit("BENCH_IO=1 decodes NCHW batches; combine with "
                         "BENCH_LAYOUT=NCHW (default)")

    import jax
    import jax.numpy as jnp
    import mxnet_tpu  # noqa: F401  (enables x64 config, registers ops)
    from mxnet_tpu.context import device_summary
    from mxnet_tpu.models.resnet import get_symbol
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    devices = _accelerators()
    n_dev = len(devices)
    sym = get_symbol(num_classes=1000, num_layers=50,
                     image_shape="3,224,224", dtype=dtype, layout=layout)
    spec = MeshSpec(make_mesh((n_dev,), ("dp",)))
    trainer = ShardedTrainer(sym, spec, lr=0.1, momentum=0.9, wd=1e-4,
                             param_dtype=dtype if dtype != "float32" else None)

    global_batch = batch * n_dev
    data_shape = (global_batch, 224, 224, 3) if layout == "NHWC" \
        else (global_batch, 3, 224, 224)
    shapes = {"data": data_shape, "softmax_label": (global_batch,)}
    params, mom, aux = trainer.init_state(shapes)

    io_mode = os.environ.get("BENCH_IO", "0") == "1"
    if os.environ.get("BENCH_AUTO_LAYOUT", "1") != "0":
        # compiler-chosen parameter layouts: kills the per-step layout
        # copies on NCHW/OIHW weights (see build_step_auto_layout).
        # The AOT executable is dtype-exact: the IO path feeds uint8.
        step, params, mom, aux = trainer.build_step_auto_layout(
            params, mom, aux, shapes,
            input_dtypes={"data": jnp.uint8} if io_mode else None)
    else:
        from mxnet_tpu.parallel.trainer import sgd_step_fn
        step = sgd_step_fn(trainer)
    keys = trainer._keys()
    guard = trainer._guard_arrays()
    if not io_mode:
        # data generated on device: the input path is not in this loop
        key = jax.random.PRNGKey(0)
        data = jax.device_put(
            jax.random.uniform(key, data_shape, jnp.float32),
            spec.batch_sharding())
        label = jax.device_put(
            jax.random.randint(key, (global_batch,), 0,
                               1000).astype(jnp.float32),
            spec.batch_sharding())
        batch_dict = {"data": data, "softmax_label": label}
    if io_mode:
        # End-to-end RecordIO mode.  Feed in CHUNKS — decode K batches on
        # the host (native OMP pipeline, overlapped with device compute on
        # the previous chunk), ship ONE uint8 superbatch, then dole out
        # batches with a single jitted dynamic-slice program (a python-int
        # slice per step would compile once per index).
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from mxnet_tpu.io.native import NativeRecordIter
        n_images = int(os.environ.get("BENCH_IO_IMAGES", "2048"))
        prefix = _ensure_bench_rec(n_images, 224)
        threads = int(os.environ.get("BENCH_IO_THREADS",
                                     str(os.cpu_count() or 8)))
        chunk = int(os.environ.get("BENCH_IO_CHUNK", "16"))
        rec_iter = NativeRecordIter(
            prefix + ".rec", (3, 224, 224), global_batch,
            idx_path=prefix + ".idx", threads=threads, shuffle=True,
            rand_mirror=True, prefetch=chunk + 2)
        # superbatch layout (K, global_batch, ...): batch axis dp-sharded so
        # pick hands each step a batch already laid out like the synthetic
        # path (spec.batch_sharding())
        x_shard = NamedSharding(spec.mesh, P(None, "dp"))

        @jax.jit
        def pick(X, L, i):
            return (lax.dynamic_index_in_dim(X, i, 0, keepdims=False),
                    lax.dynamic_index_in_dim(L, i, 0, keepdims=False))

        def decode_chunk(n):
            ds, ls = [], []
            for _ in range(n):
                try:
                    d, l, _ = rec_iter.next()
                except StopIteration:
                    rec_iter.reset()
                    d, l, _ = rec_iter.next()
                ds.append(d.astype(np.uint8))
                ls.append(l[:, 0].copy())
            return np.stack(ds), np.stack(ls)

        def run_epochs(n_iters, params, mom, aux):
            # Double-buffered: while the device steps through chunk N, the
            # host decodes chunk N+1 (native OMP queue) and ships it.
            nonlocal guard
            if n_iters <= 0:
                return params, mom, aux
            done = 0
            host = decode_chunk(min(chunk, n_iters))
            loss = None
            while done < n_iters:
                X = jax.device_put(host[0], x_shard)
                L = jax.device_put(host[1], x_shard)
                todo = host[0].shape[0]
                for i in range(todo):
                    d, l = pick(X, L, jnp.int32(i))
                    params, mom, aux, loss, _ok, guard = step(
                        params, mom, aux,
                        {"data": d, "softmax_label": l}, keys, guard)
                done += todo
                if done < n_iters:
                    # overlaps device compute
                    host = decode_chunk(min(chunk, n_iters - done))
            jax.block_until_ready(loss)
            return params, mom, aux

        params, mom, aux = run_epochs(warmup, params, mom, aux)
        t0 = time.perf_counter()
        params, mom, aux = run_epochs(iters, params, mom, aux)
        dt = time.perf_counter() - t0
    else:
        for _ in range(warmup):
            params, mom, aux, loss, _ok, guard = step(params, mom, aux, batch_dict, keys, guard)
        jax.block_until_ready(loss)

        t0 = time.perf_counter()
        for _ in range(iters):
            params, mom, aux, loss, _ok, guard = step(params, mom, aux, batch_dict, keys, guard)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0

    img_s = global_batch * iters / dt
    img_s_chip = img_s / n_dev
    result = {
        "metric": "resnet50_train_img_per_sec_per_chip" +
                  ("_io" if io_mode else ""),
        "value": round(img_s_chip, 2),
        "unit": "images/sec/chip (bs%d, %s, %s%s)" % (
            batch, dtype, layout,
            ", RecordIO+native decode in loop" if io_mode else ""),
        "vs_baseline": round(img_s_chip / BASELINE_IMG_S, 2),
        "device": device_summary(devices),
    }
    _attach_phases(result, step, n_dev, dt / iters, "resnet50")
    return result


if __name__ == "__main__":
    main()
