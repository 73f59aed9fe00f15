"""Compile-time plane tests: the persistent executable cache, the warm
standby pre-compiler and corruption quarantine (ROADMAP item 5 / PR 13).

The acceptance-level facts proven here at unit scale (the 4-proc drill
in tests/dist/dist_elastic_resize.py proves them across real process
relaunches):

* a second trainer of the same program deserializes a warm executable
  (``result=hit``) and its numerics are BIT-identical to the cold run;
* a standby pre-compile at world N makes the first step of a world-N−1
  trainer warm — zero compilation where the elastic resume would pay it;
* a corrupted cache entry (chaos ``corrupt_compile_cache``) quarantines
  and falls back to a fresh compile — never a crash, never a stale or
  wrong executable (donated programs are refused on backends whose
  deserialize path would mis-execute them).
"""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import compile as cc
from mxnet_tpu.compile import cache as cache_mod
from mxnet_tpu.compile import paths as paths_mod
from mxnet_tpu.compile import treedefs
from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
from mxnet_tpu.parallel.trainer import ShardedTrainer
from mxnet_tpu.resilience import chaos, elastic
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_cache_state():
    yield
    cache_mod.reset()
    chaos.reset()
    telemetry.reset()


@pytest.fixture
def armed(tmp_path):
    d = str(tmp_path / "ccache")
    cc.arm(d)
    return d


def _mlp():
    d = mx.sym.Variable("data")
    f1 = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    a = mx.sym.Activation(f1, act_type="relu")
    f2 = mx.sym.FullyConnected(a, num_hidden=2, name="fc2")
    return mx.sym.SoftmaxOutput(f2, name="softmax")


def _trainer(n_dev=2, accum=1):
    spec = MeshSpec(make_mesh((n_dev,), ("dp",),
                              devices=jax.devices()[:n_dev]))
    tr = ShardedTrainer(_mlp(), spec, lr=0.01, momentum=0.9, wd=0.0,
                        grad_accum=accum)
    p, m, a = tr.init_state({"data": (12 // accum, 4),
                             "softmax_label": (12 // accum,)}, seed=3)
    return tr, p, m, a


def _batches(n, rows=12):
    rs = np.random.RandomState(0)
    return [{"data": rs.randn(rows, 4).astype(np.float32),
             "softmax_label": (rs.rand(rows) > .5).astype(np.float32)}
            for _ in range(n)]


def _train(n_dev=2, accum=1, steps=2):
    tr, p, m, a = _trainer(n_dev, accum)
    for b in _batches(steps):
        p, m, a, loss = tr.step(p, m, a, b)
    return tr, [np.asarray(x).copy() for x in p]


def _last_result(name="train_step"):
    ev = [e for e in tracing._COMPILES_LOCK_FREE if e["name"] == name]
    return ev[-1].get("result") if ev else None


# ---------------------------------------------------------------------------
# treedef codec + path helper
# ---------------------------------------------------------------------------

def test_treedef_codec_roundtrip():
    for template in (0,
                     (0, 0),
                     ((0,), [0, 0], {"b": 0, "a": (0, None)}),
                     {"x": [{"y": (0,)}, None]}):
        td = jax.tree_util.tree_structure(template)
        assert treedefs.obj_to_treedef(treedefs.treedef_to_obj(td)) == td


def test_treedef_codec_rejects_custom_nodes():
    import collections
    Point = collections.namedtuple("Point", "x y")
    td = jax.tree_util.tree_structure(Point(0, 0))
    with pytest.raises(treedefs.UnsupportedTreedef):
        treedefs.treedef_to_obj(td)


def test_cache_location_convention(monkeypatch):
    # default: under <checkout>/.cache
    monkeypatch.delenv("MXNET_TPU_TESTX_CACHE", raising=False)
    loc = paths_mod.cache_location("MXNET_TPU_TESTX_CACHE", "x.json")
    assert loc == os.path.join(paths_mod.cache_root(), "x.json")
    # explicit path wins
    monkeypatch.setenv("MXNET_TPU_TESTX_CACHE", "/tmp/elsewhere.json")
    assert paths_mod.cache_location("MXNET_TPU_TESTX_CACHE",
                                    "x.json") == "/tmp/elsewhere.json"
    # "1" means "on, default location"; "0" means disabled
    monkeypatch.setenv("MXNET_TPU_TESTX_CACHE", "1")
    assert paths_mod.cache_location(
        "MXNET_TPU_TESTX_CACHE", "x.json") == os.path.join(
        paths_mod.cache_root(), "x.json")
    monkeypatch.setenv("MXNET_TPU_TESTX_CACHE", "0")
    assert paths_mod.cache_location("MXNET_TPU_TESTX_CACHE",
                                    "x.json") is None
    # the autotuner rides the same helper (the dedupe satellite)
    from mxnet_tpu.ops import autotune
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE_CACHE", "/tmp/at.json")
    assert autotune.cache_path() == "/tmp/at.json"
    monkeypatch.delenv("MXNET_TPU_AUTOTUNE_CACHE")
    assert autotune.cache_path().startswith(paths_mod.cache_root())


# ---------------------------------------------------------------------------
# the cache itself
# ---------------------------------------------------------------------------

def _toy_lowered(scale=0.1):
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    rep, bat = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))

    def step(w, x):
        return w - scale * jnp.mean(x @ w, axis=0)

    jitted = jax.jit(step, in_shardings=(rep, bat), out_shardings=rep)
    return jitted.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32),
                        jax.ShapeDtypeStruct((4, 8), jnp.float32)), mesh


def test_cache_miss_store_hit_and_run(armed):
    telemetry.arm()
    low, mesh = _toy_lowered()
    c1, r1 = cc.cached_compile(low, "toy", mesh=mesh)
    assert r1 == "miss"
    assert cc.cache_stats()["entries"] == 1
    low2, _ = _toy_lowered()
    c2, r2 = cc.cached_compile(low2, "toy", mesh=mesh)
    assert r2 == "hit"
    rep, bat = (NamedSharding(mesh, P()), NamedSharding(mesh, P("dp")))
    w = jax.device_put(np.eye(8, dtype=np.float32), rep)
    x = jax.device_put(np.ones((4, 8), np.float32), bat)
    np.testing.assert_array_equal(np.asarray(c1(w, x)),
                                  np.asarray(c2(w, x)))
    hits = telemetry.counter_total("compile.cache", result="hit")
    assert hits == 1.0


def test_cache_key_separates_call_sites(armed):
    low, mesh = _toy_lowered()
    cc.cached_compile(low, "siteA", mesh=mesh)
    low2, _ = _toy_lowered()
    _, r = cc.cached_compile(low2, "siteB", mesh=mesh)
    assert r == "miss"          # same text, different `what` -> own entry
    assert cc.cache_stats()["entries"] == 2


@pytest.mark.parametrize("mode", ["garbage", "truncate"])
def test_corrupt_entry_quarantines_and_falls_back(armed, mode):
    telemetry.arm()
    low, mesh = _toy_lowered()
    cc.cached_compile(low, "toy", mesh=mesh)
    low2, _ = _toy_lowered()
    with chaos.inject("corrupt_compile_cache", mode=mode):
        c, r = cc.cached_compile(low2, "toy", mesh=mesh)
    assert r == "miss"          # fallback compile, never a crash
    assert c is not None
    stats = cc.cache_stats()
    assert stats["quarantined"] == 1
    assert stats["entries"] == 1        # the fallback wrote a fresh entry
    assert telemetry.counter_total("compile.cache", result="corrupt") == 1.0
    # and the fresh entry is loadable again
    low3, _ = _toy_lowered()
    _, r3 = cc.cached_compile(low3, "toy", mesh=mesh)
    assert r3 == "hit"


def test_callback_programs_never_stored(armed):
    def cb(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct((4,), np.float32), x)

    low = jax.jit(cb).lower(jax.ShapeDtypeStruct((4,), jnp.float32))
    _, r = cc.cached_compile(low, "cb")
    assert r == "miss"
    assert cc.cache_stats()["entries"] == 0     # refused: result stays miss
    low2 = jax.jit(cb).lower(jax.ShapeDtypeStruct((4,), jnp.float32))
    _, r2 = cc.cached_compile(low2, "cb")
    assert r2 == "miss"


def test_donated_programs_refused_on_cpu(armed):
    """The reason the trainer builds donation-free under the cache on
    CPU: a DESERIALIZED executable with donated (aliased) inputs
    mis-executes there, so the cache must refuse to persist one."""
    assert not cc.donation_safe()       # this suite runs on XLA:CPU
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    rep = NamedSharding(mesh, P())

    def step(w):
        return w * 2.0

    jitted = jax.jit(step, in_shardings=(rep,), out_shardings=rep,
                     donate_argnums=(0,))
    low = jitted.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32))
    assert "tf.aliasing_output" in low.as_text()
    _, r = cc.cached_compile(low, "donated", mesh=mesh)
    assert r == "miss"
    assert cc.cache_stats()["entries"] == 0


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------

def test_trainer_warm_start_bit_identical(armed):
    _, ref = _train()                   # cold: miss + write-through
    assert _last_result() == "miss"
    _, warm = _train()                  # same program: hit
    assert _last_result() == "hit"
    for x, y in zip(ref, warm):
        np.testing.assert_array_equal(x, y)
    cc.disarm()
    cache_mod.reset()
    _, plain = _train()                 # cache off: the stock jit path
    assert _last_result() == "off"
    for x, y in zip(ref, plain):
        np.testing.assert_array_equal(x, y)


def test_trainer_chaos_corrupt_cache_drill(armed):
    """End-to-end through ShardedTrainer.step: a corrupted entry is
    quarantined, the step falls back to a fresh compile, training
    continues, and the counter proves which path ran."""
    telemetry.arm()
    _train()
    with chaos.inject("corrupt_compile_cache", mode="garbage"):
        _, p = _train()
    assert _last_result() == "miss"
    assert all(np.isfinite(x).all() for x in p)
    assert telemetry.counter_total("compile.cache", result="corrupt") == 1.0
    assert cc.cache_stats()["quarantined"] == 1


def test_standby_warms_smaller_world(armed):
    """The elastic shape at unit scale: a 4-device trainer pre-compiles
    the 3-device step program in the background; the real 3-device
    trainer's first step deserializes it — zero compilation where the
    resize drill would pay one."""
    tr, p, m, a = _trainer(4)
    jobs = cc.trainer_standby_jobs(
        tr, (p, m, a), [(3, 1)],
        {"data": (12, 4), "softmax_label": (12,)})
    comp = cc.StandbyCompiler(jobs).start()
    assert comp.wait(120)
    res = comp.results()["world3"]
    assert res["result"] == "standby", res
    _, warm = _train(n_dev=3)
    assert _last_result() == "hit"
    # the warm resized run must match a cold resized run bit-for-bit
    cc.disarm()
    cache_mod.reset()
    _, cold = _train(n_dev=3)
    for x, y in zip(warm, cold):
        np.testing.assert_array_equal(x, y)


def test_standby_grad_accum_variant_and_infeasible(armed):
    """Candidates carry their own grad-accum (the global-batch-constant
    rule); worlds needing more devices than visible are reported, not
    attempted."""
    tr, p, m, a = _trainer(4, accum=1)
    jobs = cc.trainer_standby_jobs(
        tr, (p, m, a), [(3, 2), (64, 1)],
        {"data": (12, 4), "softmax_label": (12,)})
    comp = cc.StandbyCompiler(jobs).start()
    assert comp.wait(120)
    res = comp.results()
    assert res["world3"]["result"] == "standby"
    assert res["world64"]["result"] == "unavailable"
    # the warmed program IS the accum-2 resized trainer's program
    _, _ = _train(n_dev=3, accum=2)
    assert _last_result() == "hit"


def test_elastic_coordinator_standby_and_manifest(armed, tmp_path):
    """ElasticCoordinator.enable_standby pre-compiles the N−1 world and
    the resize manifest records what is warm (the satellite: 'manifest
    records the pre-compiled generation')."""
    # micro 1 × world 4 × accum 3 = global batch 12; at world 3 the
    # standby keeps it constant with accum 4 (the elastic rule)
    tr, p, m, a = _trainer(4, accum=3)
    exits = []
    coord = elastic.ElasticCoordinator(
        manager=None, trainer=tr, rank=0, world=4, capacity=4,
        min_workers=3, elastic_dir=str(tmp_path), check_interval=0.0,
        on_exit=exits.append, register=False)
    sb = coord.enable_standby(
        (p, m, a), micro_batch=1,
        batch_shapes={"data": (12, 4), "softmax_label": (12,)},
        wait=True, timeout=120)
    assert sb is not None and sb.done
    report = coord.standby_report()
    assert report["complete"]
    assert report["worlds"]["world3"]["result"] in ("standby", "hit")
    assert report["cache_dir"] == cc.cache_dir()
    # a resize writes the standby report into the manifest
    assert coord.resign("test_resize", target_world=3, step=7)
    assert exits == [coord.exit_code]
    manifest = elastic.read_manifest(str(tmp_path), 1)
    assert manifest is not None
    pre = manifest.get("precompiled")
    assert pre and pre["worlds"]["world3"]["result"] in ("standby", "hit")


def test_standby_noop_when_disarmed(tmp_path):
    tr, p, m, a = _trainer(2)
    coord = elastic.ElasticCoordinator(
        manager=None, trainer=tr, rank=0, world=2, min_workers=1,
        elastic_dir=str(tmp_path), on_exit=lambda c: None, register=False)
    assert coord.enable_standby(
        (p, m, a), micro_batch=6,
        batch_shapes={"data": (12, 4), "softmax_label": (12,)}) is None
    assert coord.standby_report() is None


# ---------------------------------------------------------------------------
# autotune write-through (trials share the cache)
# ---------------------------------------------------------------------------

def test_autotune_trials_write_through_cache(armed, tmp_path, monkeypatch):
    from mxnet_tpu.ops import autotune
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    autotune.invalidate()

    def lower(cand):
        def f(x):
            return x * float(cand)
        return jax.jit(f).lower(jax.ShapeDtypeStruct((8,), jnp.float32))

    calls = []

    def measure(cand, compiled):
        calls.append(cand)
        out = compiled(jnp.ones((8,), jnp.float32))
        jax.block_until_ready(out)
        return 1.0 if cand == 2 else 2.0

    win = autotune.autotune("cc_trial", ("sig",), [1, 2], measure,
                            force=True, lower=lower)
    assert win == 2 and calls == [1, 2]
    assert cc.cache_stats()["entries"] == 2     # both trials persisted
    # a re-tune of the same candidates compiles nothing
    autotune.invalidate()
    os.unlink(str(tmp_path / "at.json"))
    telemetry.arm()
    win2 = autotune.autotune("cc_trial", ("sig",), [1, 2], measure,
                             force=True, lower=lower)
    assert win2 == 2
    assert telemetry.counter_total("compile.cache", result="hit") == 2.0


# ---------------------------------------------------------------------------
# serving artifacts: per-topology blobs + warm swap
# ---------------------------------------------------------------------------

def _export_artifact(path):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="out")
    ex = net.simple_bind(mx.cpu(), data=(4, 3))
    rs = np.random.RandomState(0)
    for arr in ex.arg_arrays:
        arr[:] = mx.nd.array(rs.normal(0, 0.3, arr.shape))
    ex.export_compiled(path, input_names=("data",))
    return path


def test_artifact_append_topology_and_warm_load(tmp_path):
    from mxnet_tpu import deploy
    from mxnet_tpu.resilience.container import read_container
    path = _export_artifact(str(tmp_path / "m.mxt"))
    _, meta, _ = read_container(path)
    fp = deploy.device_fingerprint()
    assert meta["topologies"] == {fp: "executable"}
    prog = deploy.ServedProgram.load(path)
    assert prog.load_result == "hit"    # exact AOT match = warm load
    # re-export with append=True: same topology replaces its own blob,
    # schema/weights verified, still one artifact
    _export_artifact_append(path)
    _, meta2, blobs2 = read_container(path)
    assert meta2["topologies"] == {fp: "executable"}
    prog2 = deploy.ServedProgram.load(path)
    out1 = prog.forward(data=np.ones((4, 3), np.float32))
    out2 = prog2.forward(data=np.ones((4, 3), np.float32))
    np.testing.assert_allclose(out1[0], out2[0])
    # a foreign-topology-only artifact refuses with the fingerprints
    from mxnet_tpu.resilience.container import write_container
    arrays, meta3, blobs3 = read_container(path)
    meta3 = dict(meta3)
    meta3["topologies"] = {"tpu|TPU v99|256": "executable"}
    wrong = str(tmp_path / "wrong.mxt")
    write_container(wrong, arrays=arrays, meta=meta3, blobs=blobs3)
    with pytest.raises(deploy.TopologyMismatch, match="TPU v99"):
        deploy.ServedProgram.load(wrong)


def _export_artifact_append(path):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="out")
    ex = net.simple_bind(mx.cpu(), data=(4, 3))
    rs = np.random.RandomState(0)
    for arr in ex.arg_arrays:
        arr[:] = mx.nd.array(rs.normal(0, 0.3, arr.shape))
    ex.export_compiled(path, input_names=("data",), append=True)


def test_artifact_append_refuses_different_weights(tmp_path):
    from mxnet_tpu.base import MXNetError
    path = _export_artifact(str(tmp_path / "m.mxt"))
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="out")
    ex = net.simple_bind(mx.cpu(), data=(4, 3))
    for arr in ex.arg_arrays:
        arr[:] = mx.nd.ones(arr.shape)          # different weights
    with pytest.raises(MXNetError, match="refusing to mix"):
        ex.export_compiled(path, input_names=("data",), append=True)


def test_runtime_prewarm_then_warm_swap():
    from mxnet_tpu.serving.replica import SyntheticProgram
    from mxnet_tpu.serving.runtime import ServingRuntime
    rt = ServingRuntime(SyntheticProgram(batch=4, features=3, scale=1.0),
                        linger=0.001)
    try:
        v2 = SyntheticProgram(batch=4, features=3, scale=2.0)
        rt.prewarm(v2, key="v2")
        assert rt.stats()["counters"]["prewarms"] == 1
        # old model still serving after prewarm
        out = rt.predict(data=np.ones((1, 3), np.float32), deadline=5.0)
        assert float(out[0][0][0]) == pytest.approx(1.0)
        # warm swap: flips the prewarmed standby, no revalidation
        rt.swap(v2, prewarmed="v2")
        c = rt.stats()["counters"]
        assert c["swaps"] == 1 and c["swaps_warm"] == 1
        out = rt.predict(data=np.ones((1, 3), np.float32), deadline=5.0)
        assert float(out[0][0][0]) == pytest.approx(2.0)
        # a key mismatch falls back to the validated cold path
        v3 = SyntheticProgram(batch=4, features=3, scale=3.0)
        rt.swap(v3, prewarmed="not-the-key")
        c = rt.stats()["counters"]
        assert c["swaps"] == 2 and c["swaps_warm"] == 1
    finally:
        rt.close()


def test_prewarm_rejects_bad_model_before_any_drain():
    from mxnet_tpu.serving.errors import SwapFailed
    from mxnet_tpu.serving.replica import SyntheticProgram
    from mxnet_tpu.serving.runtime import ServingRuntime
    rt = ServingRuntime(SyntheticProgram(batch=4, features=3, scale=1.0),
                        linger=0.001)
    try:
        bad = SyntheticProgram(batch=4, features=3, scale=float("nan"))
        with pytest.raises(SwapFailed, match="non-finite"):
            rt.prewarm(bad, key="bad")
        out = rt.predict(data=np.ones((1, 3), np.float32), deadline=5.0)
        assert float(out[0][0][0]) == pytest.approx(1.0)
        assert rt.stats()["counters"]["swap_failures"] == 1
    finally:
        rt.close()


# ---------------------------------------------------------------------------
# tooling: postmortem --compile + tracewatch --check over compile sinks
# ---------------------------------------------------------------------------

def test_compile_spans_land_in_trace_sink_and_tools(armed, tmp_path,
                                                    monkeypatch):
    """A traced run leaves compile/* root spans in the flight recorder;
    tracewatch --check passes over them (no orphans) and postmortem
    --compile renders the timeline with hit/miss tags + cache stats."""
    sink_dir = str(tmp_path / "sinks")
    os.makedirs(sink_dir)
    monkeypatch.setenv("MXNET_TPU_TRACE_DIR", sink_dir)
    tracing.reset()
    tracing.arm()
    try:
        _train()                         # miss
        _train()                         # hit
    finally:
        tracing.reset()
    sinks = glob.glob(os.path.join(sink_dir, "trace-*.jsonl"))
    assert sinks
    spans = [json.loads(line) for p in sinks for line in open(p)
             if line.strip()]
    compile_spans = [s for s in spans
                     if s["name"].startswith("compile/train_step")]
    results = [s.get("attrs", {}).get("result") for s in compile_spans]
    assert "miss" in results and "hit" in results

    # tracewatch --check: merged, orphan-free, exit 0
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tracewatch.py"),
         sink_dir, "--check", "--out", str(tmp_path / "merged.json")],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]

    # postmortem --compile renders the timeline + cache stats
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "postmortem.py"),
         sink_dir, "--compile", "--cache-dir", cc.cache_dir()],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COMPILE TIMELINE" in out.stdout
    assert "hit" in out.stdout and "miss" in out.stdout
    assert "CACHE" in out.stdout and "quarantined" in out.stdout


def test_compile_summary_by_result(armed):
    tracing.reset()
    _train()
    _train()
    summary = tracing.compile_summary()
    assert summary["by_result"].get("miss", 0) >= 1
    assert summary["by_result"].get("hit", 0) >= 1
    assert summary["total_seconds"] > 0
