"""Performance attribution plane.

Covers the ISSUE-6 acceptance surface:

* analytic FLOPs/bytes (analysis/costmodel.py) validated against XLA's
  own ``Compiled.cost_analysis()`` within 5% on seeded programs
  (matmul, conv, psum);
* collective accounting + the static collective/compute overlap
  instrument (including the audit_report line the dp8 dryrun prints);
* attribution reports end to end: toy jitted ShardedTrainer step smoke
  (tier-1), report schema/pretty/Perfetto counters, bench phases block;
* tools/metricsdump.py follow mode surviving truncation and rotation;
* ServingRuntime.stats() device-utilization ratio.
"""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu  # noqa: F401
from mxnet_tpu.analysis import costmodel
from mxnet_tpu.telemetry import perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# these tests run on the CPU; where one checks the roofline or the MFU
# arithmetic it says which chip's published peaks to take them against
V5E = "TPU v5 lite"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _hlo_flops(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca["flops"])


# ---------------------------------------------------------------------------
# analytic model vs XLA cost analysis (the 5% acceptance gate)
# ---------------------------------------------------------------------------

def test_analytic_flops_matmul_within_5pct():
    c = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((256, 512), jnp.float32),
        jnp.ones((512, 128), jnp.float32)).compile()
    analytic = costmodel.analytic_flops(c.as_text())["flops"]
    assert analytic == pytest.approx(2 * 256 * 512 * 128, rel=0.01)
    assert analytic == pytest.approx(_hlo_flops(c), rel=0.05)


def test_analytic_flops_conv_within_5pct():
    # strided SAME conv: exercises the padded-border and window-stride
    # discounts in the per-dim valid-tap count
    def conv(x, w):
        return jax.lax.conv_general_dilated(x, w, (2, 2), "SAME")
    c = jax.jit(conv).lower(
        jnp.ones((8, 16, 32, 32), jnp.float32),
        jnp.ones((32, 16, 3, 3), jnp.float32)).compile()
    analytic = costmodel.analytic_flops(c.as_text())["flops"]
    assert analytic == pytest.approx(_hlo_flops(c), rel=0.05)


def test_analytic_flops_conv_backward_dilated():
    # the gradient of a strided conv lowers with lhs_dilate: the zero
    # holes must be discounted or ResNet backward overcounts ~4x
    def loss(x, w):
        y = jax.lax.conv_general_dilated(x, w, (2, 2), "SAME")
        return jnp.sum(y * y)
    c = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jnp.ones((4, 8, 16, 16), jnp.float32),
        jnp.ones((16, 8, 3, 3), jnp.float32)).compile()
    analytic = costmodel.analytic_flops(c.as_text())["flops"]
    assert analytic == pytest.approx(_hlo_flops(c), rel=0.05)


def _psum_compiled():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map
    smap = lambda f, mesh: shard_map(  # noqa: E731
        f, mesh=mesh, in_specs=P("dp"), out_specs=P())
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))

    def f(x):
        return jax.lax.psum(x * 2.0, "dp")

    x = jax.device_put(jnp.ones((8, 1024), jnp.float32),
                       NamedSharding(mesh, P("dp")))
    return jax.jit(smap(f, mesh)).lower(x).compile()


def test_analytic_psum_bytes_and_flops():
    c = _psum_compiled()
    txt = c.as_text()
    from mxnet_tpu.parallel.audit import collective_accounting
    acct = collective_accounting(txt)
    # per-device shard is (1, 1024) f32 -> 4096B all-reduce payload
    assert acct["all-reduce"]["bytes"] == 4096
    assert costmodel.analytic_flops(txt)["flops"] == pytest.approx(
        _hlo_flops(c), rel=0.05)


def test_instruction_bytes_and_contributors():
    c = jax.jit(lambda a, b: (a @ b).astype(jnp.bfloat16)).lower(
        jnp.ones((64, 64), jnp.float32),
        jnp.ones((64, 64), jnp.float32)).compile()
    per_class = costmodel.instruction_bytes(c.as_text())
    split = costmodel.bytes_by_dtype(per_class)
    assert split.get("f32", 0) > 0 and split.get("bf16", 0) > 0
    top = costmodel.top_contributors(per_class, n=3)
    assert top and top[0]["bytes"] >= top[-1]["bytes"]
    assert {"op", "dtype", "bytes"} <= set(top[0])


# ---------------------------------------------------------------------------
# collective/compute overlap instrument
# ---------------------------------------------------------------------------

SYNC_HLO = """
ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %p0), replica_groups={}
  ROOT %out = f32[1024]{0} add(f32[1024]{0} %ar, f32[1024]{0} %ar)
}
"""

ASYNC_HLO = """
ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %ar-start = f32[1024]{0} all-reduce-start(f32[1024]{0} %p0), replica_groups={}
  %w = f32[1024]{0} multiply(f32[1024]{0} %p0, f32[1024]{0} %p0)
  %ar-done = f32[1024]{0} all-reduce-done(f32[1024]{0} %ar-start)
  ROOT %out = f32[1024]{0} add(f32[1024]{0} %ar-done, f32[1024]{0} %w)
}
"""


PIPELINED_SYNC_HLO = """
ENTRY %main (p0: f32[1024], q0: f32[64,64]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %q0 = f32[64,64]{1,0} parameter(1)
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %p0), replica_groups={}
  %mm = f32[64,64]{1,0} dot(f32[64,64]{1,0} %q0, f32[64,64]{1,0} %q0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %out = f32[1024]{0} add(f32[1024]{0} %ar, f32[1024]{0} %ar)
}
"""


def test_overlap_sync_is_zero():
    """A sync collective whose only neighbors are its own producers and
    consumers (no independent heavy compute) cannot be hidden by any
    scheduler: 0%."""
    ov = costmodel.collective_compute_overlap(SYNC_HLO)
    assert ov["collective_bytes"] == 4096
    assert ov["overlap_pct"] == 0.0
    assert ov["sync_ops"] == 1 and ov["async_ops"] == 0
    assert ov["pipelined_ops"] == 0


def test_overlap_pipelined_sync_counts():
    """r6 extension: a sync collective with an independent dot in the
    same computation is schedulable overlap — backends with async
    collectives (TPU) hide it; the CPU dryrun proves the schedule."""
    ov = costmodel.collective_compute_overlap(PIPELINED_SYNC_HLO)
    assert ov["sync_ops"] == 1 and ov["pipelined_ops"] == 1
    assert ov["overlapped_bytes"] == 4096
    assert ov["overlap_pct"] == 100.0
    assert ov["by_kind"]["all-reduce"]["pipelined"] == 1


def test_overlap_pipelined_ignores_ancestor_descendant_compute():
    """The dot being the collective's producer or consumer must NOT
    count — that is exactly the serialized GPipe-hop shape."""
    serial = """
ENTRY %main (q0: f32[64,64]) -> f32[64,64] {
  %q0 = f32[64,64]{1,0} parameter(1)
  %mm = f32[64,64]{1,0} dot(f32[64,64]{1,0} %q0, f32[64,64]{1,0} %q0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cp = f32[64,64]{1,0} collective-permute(f32[64,64]{1,0} %mm), source_target_pairs={{0,1},{1,0}}
  ROOT %mm2 = f32[64,64]{1,0} dot(f32[64,64]{1,0} %cp, f32[64,64]{1,0} %q0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    ov = costmodel.collective_compute_overlap(serial)
    assert ov["sync_ops"] == 1 and ov["pipelined_ops"] == 0
    assert ov["overlap_pct"] == 0.0


def test_overlap_async_with_compute_between():
    ov = costmodel.collective_compute_overlap(ASYNC_HLO)
    assert ov["async_ops"] == 1
    assert ov["overlapped_bytes"] == 4096
    assert ov["overlap_pct"] == 100.0


def test_overlap_ring_and_pipeline_schedules():
    """The r6 double-buffered parallel schedules measure overlapped on
    their boundary hops (the acceptance instrument for the dp8 dryrun
    audit): every ring ppermute is hidden; the pipeline's hop is hidden
    while its output psum (inherently after the loop) is not."""
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.ring import local_ring_attention_fn
    from jax import shard_map as smap2
    from jax.sharding import PartitionSpec as PS
    n = 2
    mesh = make_mesh((n,), ("sp",))
    fn = local_ring_attention_fn("sp", False, 0.25, n)
    spec = PS(None, "sp", None, None)
    mapped = smap2(fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
    x = jnp.ones((1, 4 * n, 2, 8), jnp.float32)
    txt = jax.jit(mapped).lower(x, x, x).compile().as_text()
    ov = costmodel.collective_compute_overlap(txt)
    assert ov["overlap_pct"] == 100.0
    assert ov["by_kind"]["collective-permute"]["pipelined"] == 2

    from mxnet_tpu.parallel.pipeline import pipeline_apply
    pp_mesh = make_mesh((n,), ("pp",))
    Ws = jnp.ones((n, 8, 8), jnp.float32) * 0.1
    xm = jnp.ones((4, 2, 8), jnp.float32)

    def run(p, xmi):
        return pipeline_apply(lambda w, v: jnp.tanh(v @ w), n, pp_mesh,
                              "pp", p, xmi)

    txt = jax.jit(run).lower(Ws, xm).compile().as_text()
    ov = costmodel.collective_compute_overlap(txt)
    cp = ov["by_kind"]["collective-permute"]
    assert cp["pipelined"] == cp["sync"], \
        "every boundary hop must be double-buffered"
    assert ov["overlapped_bytes"] >= cp["bytes"]


def test_audit_report_carries_overlap_line():
    # the dp8 dryrun's accounting line must name the overlap %
    from mxnet_tpu.parallel.audit import audit_report
    line, acct = audit_report("dp8", SYNC_HLO, 8)
    assert "collective/compute overlap" in line
    assert "all-reduce" in line and acct["all-reduce"]["count"] == 1


# ---------------------------------------------------------------------------
# attribution reports end to end
# ---------------------------------------------------------------------------

def test_attribute_compiled_report_schema(tmp_path):
    c = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((128, 128), jnp.float32),
        jnp.ones((128, 128), jnp.float32)).compile()
    rep = perf.attribute_compiled(c, "matmul", measured_step_s=1e-5,
                                  peaks_of=V5E)
    d = rep.to_dict()
    assert d["kind"] == "attribution_report"
    assert d["hlo_cost"]["flops_ratio_analytic_vs_hlo"] == pytest.approx(
        1.0, abs=0.05)
    assert d["roofline"]["bound"] in ("compute", "hbm", "collective",
                                      "host")
    shares = d["roofline"]["shares"]
    assert {"compute", "hbm", "collective", "host"} <= set(shares)
    assert d["step"]["mfu"] == pytest.approx(
        d["analytic"]["flops"] / 1e-5
        / d["roofline"]["peaks"]["flops"], rel=0.01)
    # atomic save + reload round-trip
    path = rep.save(str(tmp_path / "attr.json"))
    assert perf.AttributionReport.load(path).to_dict()["program"] \
        == "matmul"
    # pretty + perfetto renderings exist and carry the headline numbers
    text = rep.pretty()
    assert "MFU vs chip peak" in text and "roofline" in text
    counters = rep.perfetto_counters(ts_us=123.0)
    assert any(ev["ph"] == "C" and "mfu" in ev["args"]
               for ev in counters)


def test_phases_block_shape():
    c = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((64, 64), jnp.float32),
        jnp.ones((64, 64), jnp.float32)).compile()
    rep = perf.attribute_compiled(c, "bench.toy", measured_step_s=0.002,
                                  peaks_of=V5E)
    block = perf.phases_block(rep, "/tmp/r.json")
    assert {"bound", "compute_share", "hbm_share", "collective_share",
            "host_share", "mfu", "overlap_pct", "report"} <= set(block)
    assert block["report"] == "/tmp/r.json"
    assert block["mfu"] == rep.to_dict()["step"]["mfu"]


def test_toy_trainer_step_attribution_smoke(tmp_path, monkeypatch):
    """Tier-1 smoke (CI satellite): MXNET_TPU_ATTRIBUTION=1 on a toy
    jitted ShardedTrainer step writes one report with the measured step
    split folded in."""
    from mxnet_tpu import symbol as S
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu import telemetry

    monkeypatch.setenv("MXNET_TPU_ATTRIBUTION", "1")
    monkeypatch.setenv("MXNET_TPU_ATTRIBUTION_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_ATTRIBUTION_AFTER", "2")
    perf.reset_attributed()
    telemetry.reset()
    telemetry.arm()
    try:
        data = S.Variable("data")
        fc1 = S.FullyConnected(data=data, num_hidden=32, name="fc1")
        act = S.Activation(data=fc1, act_type="relu", name="relu1")
        fc2 = S.FullyConnected(data=act, num_hidden=10, name="fc2")
        sym = S.SoftmaxOutput(data=fc2, name="softmax")
        tr = ShardedTrainer(sym, MeshSpec(make_mesh((1,), ("dp",))),
                            lr=0.1)
        shapes = {"data": (8, 16), "softmax_label": (8,)}
        params, mom, aux = tr.init_state(shapes)
        rs = np.random.RandomState(0)
        feed = {"data": rs.rand(8, 16).astype(np.float32),
                "softmax_label": rs.randint(0, 10, 8).astype(np.float32)}
        for _ in range(3):
            params, mom, aux, loss = tr.step(params, mom, aux, feed)
        assert np.isfinite(float(loss))
    finally:
        telemetry.disarm()
        telemetry.reset()
    reports = [f for f in os.listdir(str(tmp_path))
               if f.startswith("attribution-") and f.endswith(".json")]
    assert len(reports) == 1
    d = json.load(open(os.path.join(str(tmp_path), reports[0])))
    assert d["program"].startswith("ShardedTrainer.step")
    assert d["analytic"]["flops"] > 0
    assert d["step"]["measured_s"] > 0
    assert d["step"]["host_enqueue_s"] is not None
    assert d["hlo_cost"]["flops_ratio_analytic_vs_hlo"] == pytest.approx(
        1.0, abs=0.10)
    # a second trainer step must NOT write a second report (once per
    # program)
    params, mom, aux, _ = tr.step(params, mom, aux, feed)
    assert len([f for f in os.listdir(str(tmp_path))
                if f.startswith("attribution-")]) == 1


def test_transformer_attribution_matches_bench_formula():
    """The library's analytic FLOPs from the compiled transformer step
    agree with the benchmark's formula (benchmark/lib/flops.py) within
    5% — which bounds |attribution MFU - benchmark MFU| by 0.02 at MFU
    0.4."""
    from mxnet_tpu.models.transformer import get_symbol
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    # mid-size geometry: dots must dominate enough that the matmul-only
    # formula and the full-program analytic count agree within 5% (at
    # gpt2s.train-b16's L12/H768/T1024 the elementwise share is smaller
    # still)
    batch, seq, layers, hidden, heads, vocab = 2, 256, 2, 1024, 8, 2048
    sym = get_symbol(vocab_size=vocab, seq_len=seq, num_layers=layers,
                     hidden=hidden, heads=heads)
    tr = ShardedTrainer(sym, MeshSpec(make_mesh((1,), ("dp",))),
                        lr=1e-4, wd=0.0, param_dtype="bfloat16")
    shapes = {"data": (batch, seq), "softmax_label": (batch, seq)}
    params, mom, aux = tr.init_state(shapes)
    step, params, mom, aux = tr.build_step_auto_layout(
        params, mom, aux, shapes)
    rep = perf.attribute_compiled(step, "transformer",
                                  measured_step_s=0.1, peaks_of=V5E)
    d = rep.to_dict()
    from benchmark.lib import flops
    cfg = {"n_embd": hidden, "n_inner": None, "n_layer": layers,
           "vocab_size": vocab}
    # the benchmark counts the causal pairs only (what the algorithm
    # requires); below _FLASH_MIN_SEQ the step's einsum path multiplies
    # the masked pairs too, so they are added back for this comparison
    masked_pairs = seq * seq - seq * (seq + 1) // 2
    formula = (flops.lm_train_flops_per_step(cfg, batch, seq)
               + 3 * batch * layers * 4 * masked_pairs * hidden)
    assert d["analytic"]["flops_by_op"]["dot"] == formula
    assert d["analytic"]["flops"] == pytest.approx(formula, rel=0.05)
    assert d["analytic"]["flops"] == pytest.approx(
        d["hlo_cost"]["flops"], rel=0.05)
    # MFU consistency: same measured time + flops within 5% -> MFU
    # within 0.02 at a 0.4 operating point
    peak = d["roofline"]["peaks"]["flops"]
    bench_mfu = formula / 0.1 / peak
    assert abs(d["step"]["mfu"] - bench_mfu) <= 0.05 * bench_mfu + 1e-9
    # the r5 accounting the report must reproduce: dtype split with
    # named top contributors
    assert d["analytic"]["bytes_by_dtype"]
    assert len(d["analytic"]["top_contributors"]) >= 3


# ---------------------------------------------------------------------------
# the compact phases block
# ---------------------------------------------------------------------------

def test_phases_block_and_report_carry_collective_bytes():
    """bench phases block exposes the per-step wire bytes; multi-device
    programs attribute them per mesh axis in the report."""
    c = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((32, 32), jnp.float32),
        jnp.ones((32, 32), jnp.float32)).compile()
    rep = perf.attribute_compiled(c, "bench.toy", measured_step_s=0.001)
    block = perf.phases_block(rep)
    assert block["collective_bytes_per_step"] == 0   # single-chip toy

    if len(jax.devices()) >= 4:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
        spec = MeshSpec(make_mesh((4,), ("dp",)))
        bat = NamedSharding(spec.mesh, P("dp"))
        rep_s = spec.replicated()
        cd = jax.jit(lambda x: jnp.sum(x, axis=0),
                     in_shardings=bat, out_shardings=rep_s).lower(
            jnp.ones((8, 128), jnp.float32)).compile()
        r = perf.attribute_compiled(cd, "dp.toy", n_devices=4,
                                    mesh=spec.mesh)
        d = r.to_dict()["analytic"]
        assert d["collectives_by_axis"].get("dp", 0) > 0
        assert perf.phases_block(r)["collective_bytes_per_step"] > 0
        assert "collective bytes by axis" in r.pretty()


# ---------------------------------------------------------------------------
# metricsdump follow survives truncation/rotation
# ---------------------------------------------------------------------------

def test_metricsdump_follow_reader_truncate_and_rotate(tmp_path):
    md = _load_tool("metricsdump")
    path = str(tmp_path / "feed.jsonl")
    with open(path, "w") as f:
        f.write('{"time": 1, "metrics": {}}\n')
    reader = md.FollowReader(path)
    try:
        assert len(reader.poll()) == 1
        with open(path, "a") as f:
            f.write('{"time": 2, "metrics": {}}\n')
        assert len(reader.poll()) == 1
        # truncation (exporter restarted with a fresh file)
        with open(path, "w") as f:
            f.write('{"time": 3, "metrics": {}}\n')
        assert [s["time"] for s in reader.poll()] == [3]
        # rotation: file disappears, then a NEW inode takes the name
        os.remove(path)
        assert reader.poll() == []
        side = str(tmp_path / "fresh.jsonl")
        with open(side, "w") as f:
            f.write('{"time": 4, "metrics": {}}\n')
        os.replace(side, path)
        assert [s["time"] for s in reader.poll()] == [4]
    finally:
        reader.close()


# ---------------------------------------------------------------------------
# serving device-utilization satellite
# ---------------------------------------------------------------------------

class _SleepProgram:
    input_names = ["data"]
    input_shapes = {"data": (4, 8)}
    input_dtypes = {"data": np.dtype(np.float32)}
    output_shapes = [(4, 8)]

    def __init__(self, latency):
        self.latency = latency

    def forward(self, data):
        time.sleep(self.latency)
        return [np.asarray(data)]


def test_serving_stats_device_utilization():
    from mxnet_tpu.serving import ServingRuntime
    with ServingRuntime(_SleepProgram(0.01),
                        default_deadline=5.0) as rt:
        for _ in range(5):
            rt.submit({"data": np.ones((1, 8), np.float32)}) \
              .result(timeout=5)
        s = rt.stats()
    assert 0.0 < s["device_utilization"] <= 1.0
    # additive: the pre-existing schema is intact
    assert {"health", "queue_depth", "exec_time_ewma_s",
            "counters"} <= set(s)
