"""Memory observability plane (ISSUE 7): tag bucketing, the sampler's
gauges/timeline/counter-track, the attribution report's memory section
(predicted vs compiled within 20% on the trainer + ring entry points),
the OOM drill (chaos ``oom`` fault -> post-mortem naming the top
consumer and the tripping program, rendered by tools/memwatch.py),
the leak watchdog, digest/fleet memory columns, the checkpoint-restore
double-residency fix, and the disarmed zero-cost gate.
"""
import gc
import glob
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.telemetry import memory
from mxnet_tpu.resilience import chaos, watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_MEMWATCH", raising=False)
    monkeypatch.delenv("MXNET_TPU_DEVICE_HBM_GB", raising=False)
    telemetry.reset()
    telemetry.disarm()
    chaos.reset()
    watchdog.reset()
    yield
    profiler.set_state("stop")
    telemetry.reset()
    telemetry.disarm()
    chaos.reset()
    watchdog.reset()


def _toy_trainer(n_dev=2, hidden=64):
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")
    spec = MeshSpec(make_mesh((min(n_dev, jax.device_count()),), ("dp",)))
    trainer = ShardedTrainer(net, spec, lr=0.1)
    shapes = {"data": (8, 32), "softmax_label": (8,)}
    return trainer, trainer.init_state(shapes), shapes


# ---------------------------------------------------------------------------
# tagging + live accounting
# ---------------------------------------------------------------------------

def test_tag_bucketing_roundtrip():
    telemetry.arm()
    a = jnp.ones((128, 128))            # 64 KB
    b = jnp.ones((64, 64))              # 16 KB
    memory.tag(a, "params", label="t.a")
    memory.tag({"x": [b]}, "optimizer", label="t.b")   # nested trees walk
    by_tag = memory.live_bytes_by_tag()
    assert by_tag["params"] == a.nbytes
    assert by_tag["optimizer"] == b.nbytes
    assert by_tag["total"] >= a.nbytes + b.nbytes
    rows = {r["label"]: r for r in memory.live_buffers() if r["label"]}
    assert rows["t.a"]["tag"] == "params"
    assert rows["t.a"]["shape"] == [128, 128]
    # tags are weak: a deleted buffer leaves the accounting
    a.delete()
    assert memory.live_bytes_by_tag().get("params", 0) == 0


def test_tagging_unwraps_ndarray_handles():
    telemetry.arm()
    nd = mx.nd.array(np.ones((32, 32), np.float32))
    memory.tag([nd], "batch", label="nd")
    assert memory.tagged_bytes("batch") >= nd._handle.nbytes


def test_disarmed_is_zero_cost_and_tracks_nothing():
    assert not memory.enabled()
    x = jnp.ones((16,))
    memory.tag(x, "params")
    memory.note_step(1)
    telemetry.arm()
    assert all(r["tag"] == "untagged" for r in memory.live_buffers()
               if r["shape"] == [16])
    telemetry.disarm()
    memory.reset()
    # per-call cost of the disarmed gates (tag + note_step + oom_guard):
    # the generous PR-5 bound — a live_arrays walk or a lock would blow it
    tree = {"data": None}
    n = 3000
    t0 = time.perf_counter()
    for i in range(n):
        with memory.oom_guard("t/hot", step=i):
            memory.tag(tree, "batch")
        memory.note_step(i)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 50e-6, "disarmed memory hooks cost %.1fus" % (
        per_call * 1e6)


def test_memwatch_env_gate_overrides_telemetry(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_MEMWATCH", "1")
    memory.reset()
    assert memory.enabled()             # armed without telemetry
    monkeypatch.setenv("MXNET_TPU_MEMWATCH", "0")
    memory.reset()
    telemetry.arm()
    assert not memory.enabled()         # explicit off beats telemetry


def test_sampler_gauges_timeline_and_counter_track(tmp_path):
    telemetry.arm()
    big = jnp.ones((256, 256))          # 256 KB
    memory.tag(big, "params", label="sampled")
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    profiler.set_state("run")
    memory.sample_now()
    profiler.set_state("stop")
    assert telemetry.gauge("mem.live_bytes").value(
        tag="params") >= big.nbytes
    assert telemetry.gauge("mem.live_bytes_total").value() >= big.nbytes
    assert telemetry.gauge("mem.peak_live_bytes").value() >= big.nbytes
    win = memory.memory_window()
    assert win["samples"] and win["peak_live_bytes"] >= big.nbytes
    assert win["samples"][-1]["by_tag"]["params"] >= big.nbytes
    # the live-HBM counter track landed in the merged Perfetto trace
    path = profiler.dump_profile()
    events = json.load(open(path))["traceEvents"]
    counters = [e for e in events if e["ph"] == "C"
                and e["name"] == "memory/live_bytes"]
    assert counters, "no live-HBM counter track in the merged trace"
    assert counters[0]["args"]["params"] >= big.nbytes


def test_release_frees_and_reports_bytes():
    x = jnp.ones((64, 64))
    y = jnp.ones((32,))
    want = x.nbytes + y.nbytes
    freed = memory.release({"a": x, "b": (y,)})
    assert freed == want
    assert x.is_deleted() and y.is_deleted()
    assert memory.release(x) == 0       # idempotent


# ---------------------------------------------------------------------------
# attribution memory section (acceptance: trainer + ring within 20%)
# ---------------------------------------------------------------------------

def _memory_section_of(compiled, name):
    from mxnet_tpu.telemetry import perf
    return perf.attribute_compiled(compiled, name).to_dict()["memory"]


def test_attribution_memory_section_schema():
    x = jnp.ones((128, 128))
    compiled = jax.jit(lambda a: a @ a).lower(x).compile()
    mem = _memory_section_of(compiled, "toy_matmul")
    assert mem["predicted"]["argument_bytes"] == x.nbytes
    assert mem["predicted"]["output_bytes"] == x.nbytes
    comp = mem["compiled"]
    assert set(comp) >= {"argument_bytes", "output_bytes", "temp_bytes",
                         "alias_bytes", "peak_bytes"}
    assert 0.8 <= mem["predicted_vs_compiled"] <= 1.2
    # phases block surfaces the peak for bench artifacts
    from mxnet_tpu.telemetry import perf
    rep = perf.attribute_compiled(compiled, "toy_matmul")
    block = perf.phases_block(rep)
    assert block["peak_hbm_bytes"] == comp["peak_bytes"]


def test_trainer_step_memory_predicted_vs_compiled_within_20pct():
    trainer, (params, mom, aux), shapes = _toy_trainer()
    from mxnet_tpu.parallel.trainer import sgd_step_fn
    step = sgd_step_fn(trainer)
    inputs = {n: jnp.zeros(s, jnp.float32) for n, s in shapes.items()}
    keys = trainer._keys()
    guard = trainer._guard_arrays()

    def sds(t):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)

    compiled = step.lower(*sds((params, mom, aux, inputs, keys,
                                guard))).compile()
    mem = _memory_section_of(compiled, "trainer_step")
    assert mem.get("compiled"), "no memory_analysis on this backend?"
    ratio = mem["predicted_vs_compiled"]
    assert ratio is not None and 0.8 <= ratio <= 1.2, ratio


def test_ring_memory_predicted_vs_compiled_within_20pct():
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.ring import local_ring_attention_fn
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    n = min(2, jax.device_count())
    mesh = make_mesh((n,), ("sp",))
    fn = local_ring_attention_fn("sp", causal=True, scale=1.0,
                                 num_devices=n)
    mapped = shard_map(fn, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                       out_specs=P(None, "sp"))
    blk = jnp.ones((1, 2 * n, 2, 4), jnp.float32)
    compiled = jax.jit(mapped).lower(blk, blk, blk).compile()
    mem = _memory_section_of(compiled, "ring_attention")
    assert mem.get("compiled"), "no memory_analysis on this backend?"
    ratio = mem["predicted_vs_compiled"]
    assert ratio is not None and 0.8 <= ratio <= 1.2, ratio


# ---------------------------------------------------------------------------
# OOM drill: chaos fault -> forensics -> memwatch --report
# ---------------------------------------------------------------------------

def test_oom_drill_postmortem_and_memwatch_report(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_WATCHDOG_DIR", str(tmp_path))
    # the report's table holds the LARGEST live buffers: what an earlier
    # test of this worker left for the collector (a GPT-2-sized program's
    # weights, in a cycle) would crowd this trainer's out of it
    gc.collect()
    telemetry.arm()
    trainer, (params, mom, aux), shapes = _toy_trainer(hidden=512)
    batch = {"data": np.random.rand(8, 32).astype(np.float32),
             "softmax_label": np.zeros(8, np.float32)}
    # a warm step so the armed plane has tags + a timeline sample
    params, mom, aux, loss = trainer.step(params, mom, aux, batch)
    with chaos.inject("oom", at_step=2):
        with pytest.raises(Exception) as ei:
            trainer.step(params, mom, aux, batch)
    assert memory.is_oom(ei.value)
    reports = glob.glob(str(tmp_path / "oom-postmortem-*.json"))
    assert len(reports) == 1
    doc = json.load(open(reports[0]))
    assert doc["kind"] == "oom_postmortem"
    assert doc["tag"] == "ShardedTrainer.step"
    assert "ShardedTrainer.step" in doc["program"]
    assert "RESOURCE_EXHAUSTED" in doc["error"]
    # the report names the top live consumers WITH their tags: the
    # trainer's fc1 weight (512x32 f32) must be in the table as params
    tagged = [r for r in doc["top_buffers"]
              if r["tag"] == "params" and r["nbytes"] >= 512 * 32 * 4]
    assert tagged, doc["top_buffers"][:5]
    assert doc["live_bytes_by_tag"]["params"] > 0
    assert doc["timeline"]["samples"], "no memory timeline in report"
    assert doc["hint"]
    assert telemetry.counter_total("mem.oom") == 1
    assert telemetry.counter_total("chaos.faults_injected") >= 1

    # tools/memwatch.py --report renders the forensics (stdlib only)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "memwatch.py"),
         "--report", reports[0], "--top", "5"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "OOM POST-MORTEM" in out.stdout
    assert "params" in out.stdout
    assert "hint:" in out.stdout
    assert "RESOURCE_EXHAUSTED" in out.stdout


def test_oom_guard_passes_through_non_oom_errors(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_WATCHDOG_DIR", str(tmp_path))
    with pytest.raises(ValueError):
        with memory.oom_guard("t"):
            raise ValueError("not an oom")
    assert glob.glob(str(tmp_path / "oom-postmortem-*")) == []


# ---------------------------------------------------------------------------
# leak watchdog
# ---------------------------------------------------------------------------

def test_leak_watchdog_flags_synthetic_growing_cache():
    wd = memory.LeakWatchdog(window=12, min_samples=8,
                             threshold_bytes=1e6)
    for step in range(10):
        wd.observe(step, 10e6 + step * 0.5e6)     # +0.5 MB per step
    rep = wd.check()
    assert rep is not None
    assert rep["growth_bytes"] == pytest.approx(4.5e6)
    assert rep["kind"] == "leak_suspected"


def test_leak_watchdog_ignores_plateau_and_noise():
    wd = memory.LeakWatchdog(window=12, min_samples=8,
                             threshold_bytes=1e6)
    for step in range(10):                        # plateau after warmup
        wd.observe(step, 10e6 + min(step, 3) * 1e6)
    assert wd.check() is None
    wd.reset()
    for step in range(10):                        # sawtooth (GC'd cache)
        wd.observe(step, 10e6 + (step % 2) * 5e6)
    assert wd.check() is None


def test_leak_watchdog_end_to_end_via_note_step(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_MEMWATCH_LEAK_MB", "5")
    memory.reset()                                # re-reads the threshold
    telemetry.arm()
    cache = []                                    # the leak
    for step in range(10):
        cache.append(memory.tag(jnp.ones((256, 1024), jnp.float32),
                                "activations", label="leaky"))  # 1 MB each
        memory.note_step(step, min_interval=0.0)
    rep = memory.leak_report()
    assert rep is not None and rep["growth_bytes"] >= 8e6
    assert telemetry.counter_total("mem.leak_suspected") >= 1


# ---------------------------------------------------------------------------
# digests + fleet view memory columns
# ---------------------------------------------------------------------------

def test_digest_and_fleet_view_carry_memory_columns(monkeypatch):
    from tests.test_watchdog import FakeKVClient
    telemetry.arm()
    held = memory.tag(jnp.ones((512, 512)), "params", label="digest")
    assert held is not None               # keep the buffer live
    memory.sample_now()
    d = telemetry.rank_digest(step=7)
    assert d["mem_mb"]["live"] >= 1.0
    assert d["mem_mb"]["peak"] >= d["mem_mb"]["live"] - 0.1

    client = FakeKVClient()
    lane = watchdog.HeartbeatLane(client=client)
    monkeypatch.setattr(watchdog, "_LANE", lane)
    assert lane.beat(7, force=True)
    digests = lane.digests()
    assert digests[0]["mem_mb"]["live"] >= 1.0
    view = telemetry.fleet_view()
    assert view["ranks"]["0"]["digest"]["mem_mb"]["peak"] >= 1.0
    rendered = telemetry.render_fleet(view)
    assert "live_mb" in rendered and "peak_mb" in rendered


# ---------------------------------------------------------------------------
# checkpoint restore: the double-residency fix
# ---------------------------------------------------------------------------

def test_restore_trainer_releases_old_state_before_device_put(tmp_path):
    """The ~2x-peak fix: with ``old_state`` passed, every OLD device
    buffer is freed BEFORE the first device_put of the restored tree —
    peak residency stays ~1x model size (old is gone while new
    materializes) instead of old+new."""
    from mxnet_tpu.resilience.checkpoint import (CheckpointManager,
                                                 restore_trainer,
                                                 save_trainer)
    trainer, (params, mom, aux), shapes = _toy_trainer()
    mgr = CheckpointManager(str(tmp_path), keep=2)
    save_trainer(mgr, trainer, params, mom, aux, step=5)

    old_leaves = [x for x in (*params, *mom, *aux)]
    model_bytes = sum(x.nbytes for x in old_leaves)
    real_device_put = jax.device_put
    old_alive_at_put = []

    def spying_put(value, *a, **kw):
        old_alive_at_put.append(
            sum(x.nbytes for x in old_leaves if not x.is_deleted()))
        return real_device_put(value, *a, **kw)

    jax.device_put = spying_put
    try:
        out = restore_trainer(mgr, trainer,
                              old_state=(params, mom, aux))
    finally:
        jax.device_put = real_device_put
    assert out is not None
    new_params, new_mom, new_aux, step, _meta = out
    assert step == 5
    assert old_alive_at_put, "restore made no device_put calls?"
    # at EVERY materialization point the old residency was zero
    assert max(old_alive_at_put) == 0, (
        "old state still resident during restore: peak would be ~2x "
        "(%d of %d bytes live)" % (max(old_alive_at_put), model_bytes))
    assert all(x.is_deleted() for x in old_leaves)
    # the restored state is whole and usable
    batch = {"data": np.random.rand(8, 32).astype(np.float32),
             "softmax_label": np.zeros(8, np.float32)}
    _p, _m, _a, loss = trainer.step(new_params, new_mom, new_aux, batch)
    assert np.isfinite(float(loss))


def test_restore_trainer_without_old_state_keeps_legacy_behavior(tmp_path):
    from mxnet_tpu.resilience.checkpoint import (CheckpointManager,
                                                 restore_trainer,
                                                 save_trainer)
    trainer, (params, mom, aux), shapes = _toy_trainer()
    mgr = CheckpointManager(str(tmp_path), keep=2)
    save_trainer(mgr, trainer, params, mom, aux, step=3)
    out = restore_trainer(mgr, trainer)
    assert out is not None
    assert not params[0].is_deleted()   # caller's references untouched


# ---------------------------------------------------------------------------
# GC501 + capacity plumbing (memory side; graphcheck side in
# tests/test_analysis.py)
# ---------------------------------------------------------------------------

def test_device_capacity_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_DEVICE_HBM_GB", "32")
    assert memory.device_capacity_bytes() == 32e9


def test_predicted_peak_bytes_donation_accounting():
    from mxnet_tpu.analysis import costmodel
    assert costmodel.predicted_peak_bytes(100, 10, donated=True) == 110
    assert costmodel.predicted_peak_bytes(100, 10, donated=False) == 210
    assert costmodel.predicted_peak_bytes(100, 10, temp_bytes=5) == 115


# ---------------------------------------------------------------------------
# memwatch live-tail rendering (the gauge console)
# ---------------------------------------------------------------------------

def test_memwatch_tails_mem_gauges_from_jsonl(tmp_path):
    telemetry.arm()
    held = memory.tag(jnp.ones((512, 512)), "served", label="tail")
    assert held is not None               # keep the buffer live
    memory.sample_now()
    feed = str(tmp_path / "metrics.jsonl")
    telemetry.export_jsonl(feed)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "memwatch.py"),
         feed], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "live" in out.stdout and "served" in out.stdout
    assert "MB" in out.stdout
