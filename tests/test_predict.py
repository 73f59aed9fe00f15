"""Prediction-conformance plane tests (ISSUE 20).

Covers the calibration store (roundtrip, running-mean updates, fallback
ladder), pre-flight budgets + env-limit gating, the
conformance verdict bands, the CI-gated prediction-agreement loop for
the trainer and ring entry points, input-bound detection on a genuinely
starved toy run, and the fleet-level drill where a rank slow against its
OWN budget is fingered through the heartbeat-digest conformance column.
"""
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.analysis import predict
from mxnet_tpu.io import DataIter, DataBatch, NDArrayIter
from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
from mxnet_tpu.parallel.ring import local_ring_attention_fn
from mxnet_tpu.parallel.trainer import ShardedTrainer
from mxnet_tpu.resilience import watchdog
from mxnet_tpu.telemetry import perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

@pytest.fixture(autouse=True)
def _isolated_plane(tmp_path, monkeypatch):
    """Every test gets its own calibration store + clean noted budgets;
    nothing leaks into (or reads) the checkout's .cache store."""
    monkeypatch.setenv("MXNET_TPU_CALIBRATION_CACHE",
                       str(tmp_path / "calibration.json"))
    for var in ("MXNET_TPU_STEP_BUDGET_MS", "MXNET_TPU_WIRE_BUDGET_MB",
                "MXNET_TPU_DEVICE_HBM_GB", "MXNET_TPU_THROUGHPUT_FLOOR"):
        monkeypatch.delenv(var, raising=False)
    predict.reset()
    telemetry.reset()
    yield
    telemetry.disarm()
    telemetry.reset()
    predict.reset()


def _toy_compiled(n=128):
    return jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((n, n), jnp.float32),
        jnp.ones((n, n), jnp.float32)).compile()


# ---------------------------------------------------------------------------
# calibration store
# ---------------------------------------------------------------------------

def test_store_roundtrip_and_running_mean(tmp_path):
    path = str(tmp_path / "c.json")
    store = predict.load_store(path)
    assert store["entries"] == {}
    predict.update_calibration(store, "cpu", "compute", 0.4)
    predict.update_calibration(store, "cpu", "compute", 0.6)
    e = store["entries"]["cpu|compute"]
    assert e["achievable_fraction"] == pytest.approx(0.5)
    assert e["n"] == 2
    saved = predict.save_store(store, path)
    assert saved == path
    back = predict.load_store(path)
    assert back["entries"]["cpu|compute"]["n"] == 2
    assert back["fitted_t"] > 0
    # corrupt file degrades to an empty store, never raises
    with open(path, "w") as fh:
        fh.write("{nope")
    assert predict.load_store(path)["entries"] == {}
    # fractions are clamped into (0, 1]
    predict.update_calibration(store, "cpu", "hbm", 7.5)
    assert store["entries"]["cpu|hbm"]["achievable_fraction"] == 1.0


def test_achievable_fraction_fallback_ladder():
    store = {"entries": {
        "tpu v4|compute": {"achievable_fraction": 0.42, "n": 9,
                           "source": "telemetry"},
        "tpu v4|hbm": {"achievable_fraction": 0.62, "n": 3,
                       "source": "ledger"}}}
    # exact entry
    hit = predict.achievable_fraction(store, "tpu v4", "compute")
    assert hit["fraction"] == 0.42 and hit["source"] == "telemetry"
    # same kind, other bucket: nearest-bucket mean
    near = predict.achievable_fraction(store, "tpu v4", "collective")
    assert near["fraction"] == pytest.approx((0.42 + 0.62) / 2)
    assert near["source"] == "nearest-bucket"
    # unknown kind: the documented default
    miss = predict.achievable_fraction(store, "gpu", "compute")
    assert miss["fraction"] == predict.DEFAULT_FRACTION
    assert miss["source"] == "default" and miss["n"] == 0


# ---------------------------------------------------------------------------
# budgets + gating
# ---------------------------------------------------------------------------

def test_predict_budget_shape_and_table():
    rep = predict.predict_budget(_toy_compiled(), "toy",
                                 items_per_step=128)
    assert rep["kind"] == "predict_report"
    b = rep["budget"]
    assert b["step_time_s"] > 0 and b["peak_hbm_bytes"] > 0
    # step_time_s is rounded to ns in the report; the throughput was
    # computed from the exact value
    assert b["throughput_per_s"] == pytest.approx(
        128 / b["step_time_s"], rel=0.01)
    assert rep["basis"]["bound"] in ("compute", "hbm", "collective")
    assert 0 < rep["basis"]["achievable_fraction"] <= 1.0
    assert rep["over_budget"] == []
    table = predict.budget_table([rep])
    assert "toy" in table and "ok" in table
    # the budget was noted for later runtime conformance
    assert predict.noted_budget("toy")["budget"] == b


def test_budget_gating_from_env_limits(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_STEP_BUDGET_MS", "0.000001")
    monkeypatch.setenv("MXNET_TPU_THROUGHPUT_FLOOR", "1e18")
    rep = predict.predict_budget(_toy_compiled(), "gated",
                                 items_per_step=4)
    assert set(rep["over_budget"]) == {"step_time_s", "throughput_per_s"}
    assert "OVER BUDGET" in predict.budget_table([rep])
    # decode budgets gate through the same limits
    drep = predict.predict_decode_budget(2, 64, 256, 4, 128,
                                         name="decode-gated")
    assert "step_time_s" in drep["over_budget"]


def test_decode_budget_model():
    rep = predict.predict_decode_budget(2, 64, 256, 4, 128, quant_bits=8,
                                        name="decode8")
    wide = predict.predict_decode_budget(2, 64, 256, 4, 128,
                                         quant_bits=32, name="decode32")
    assert rep["budget"]["step_time_s"] > 0
    # quantized weights move fewer bytes -> cheaper hbm-bound step
    assert rep["basis"]["hbm_bytes"] < wide["basis"]["hbm_bytes"]
    assert rep["budget"]["throughput_per_s"] > 0


# ---------------------------------------------------------------------------
# conformance verdicts
# ---------------------------------------------------------------------------

def test_conformance_bands_floor_and_sigma():
    flat = predict.conformance_bands([])
    assert flat["basis"] == "floor"
    assert flat["degraded_tolerance"] == predict.CONFORMANCE_FLOOR
    assert flat["violated_tolerance"] == 2 * predict.CONFORMANCE_FLOOR
    # a genuinely noisy history widens the band past the floor
    noisy = predict.conformance_bands([1.0, 0.7, 1.1, 0.6, 1.2, 0.65])
    assert noisy["basis"] == "sigma"
    assert noisy["degraded_tolerance"] > predict.CONFORMANCE_FLOOR


def test_conformance_verdict_ladder():
    budget = {"program": "p", "budget": {"step_time_s": 1.0,
                                         "throughput_per_s": 100.0},
              "basis": {"calibration_source": "ledger"}}
    within = predict.conformance(budget, {"step_time_s": 1.1})
    assert within["verdict"] == "WITHIN"
    degraded = predict.conformance(budget, {"step_time_s": 1.3})
    assert degraded["metrics"]["step_time_s"]["verdict"] == "DEGRADED"
    violated = predict.conformance(budget, {"step_time_s": 2.0})
    assert violated["verdict"] == "VIOLATED"
    assert violated["metrics"]["step_time_s"]["ratio"] == 2.0
    assert violated["calibration_source"] == "ledger"
    # higher-is-better metrics invert: 2x the promised tokens is WITHIN
    toks = predict.conformance(budget, {"decode_tokens_per_s": 200.0})
    assert toks["verdict"] == "WITHIN"
    starved = predict.conformance(budget, {"decode_tokens_per_s": 40.0})
    assert starved["verdict"] == "VIOLATED"
    # nothing comparable -> None
    assert predict.conformance(budget, {"unknown_metric": 1.0}) is None


def test_digest_column_picks_worst():
    budget = {"program": "a", "budget": {"step_time_s": 1.0}}
    predict.note_budget("a", budget)
    predict.runtime_conformance(
        "a", {"step": {"measured_s": 1.05}})
    budget2 = {"program": "b", "budget": {"step_time_s": 1.0}}
    predict.note_budget("b", budget2)
    predict.runtime_conformance(
        "b", {"step": {"measured_s": 1.9}})
    col = predict.digest_column()
    assert col["program"] == "b" and col["verdict"] == "VIOLATED"
    assert col["metric"] == "step_time_s"
    assert col["ratio"] == pytest.approx(1.9)
    predict.reset()
    assert predict.digest_column() is None


# ---------------------------------------------------------------------------
# prediction agreement (the CI-gated ~20% acceptance for trainer + ring)
# ---------------------------------------------------------------------------

def _agreement(compiled, name, measured_s, tmp_path):
    """Calibrate from one attributed run, then predict with the fitted
    store: the budget must land within the conformance floor (20%) of
    what was measured."""
    data = perf.attribute_compiled(
        compiled, name, measured_step_s=measured_s,
        peaks_of=predict.TARGET_DEVICE_KIND).to_dict()
    store = predict.load_store(str(tmp_path / "agree.json"))
    assert predict.fit_from_attribution(store, data) is not None
    rep = predict.predict_budget(compiled, name, store=store)
    assert rep["basis"]["calibration_source"] == "telemetry"
    predicted = rep["budget"]["step_time_s"]
    assert predicted == pytest.approx(measured_s, rel=0.20), \
        "%s: predicted %.3g vs measured %.3g" % (name, predicted,
                                                 measured_s)


def test_trainer_prediction_agreement(tmp_path):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=256, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=64, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")
    tr = ShardedTrainer(net, MeshSpec(make_mesh((1,), ("dp",))), lr=0.1)
    shapes = {"data": (64, 256), "softmax_label": (64,)}
    params, mom, aux = tr.init_state(shapes)
    rs = np.random.RandomState(0)
    feed = {"data": rs.rand(64, 256).astype(np.float32),
            "softmax_label": rs.randint(0, 10, 64).astype(np.float32)}
    for _ in range(3):                                   # compile + warm
        params, mom, aux, _ = tr.step(params, mom, aux, feed)
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        params, mom, aux, loss = tr.step(params, mom, aux, feed)
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
    measured = sorted(times)[len(times) // 2]
    inputs = {k: jax.ShapeDtypeStruct(v, jnp.float32)
              for k, v in shapes.items()}
    jitted = tr._step or tr._build_step()
    compiled = jitted.lower(params, mom, aux, inputs, tr._keys(),
                            tr._guard_arrays()).compile()
    _agreement(compiled, "trainer", measured, tmp_path)


def test_ring_prediction_agreement(tmp_path):
    n = min(2, jax.device_count())
    mesh = make_mesh((n,), ("sp",))
    fn = local_ring_attention_fn("sp", causal=True, scale=1.0,
                                 num_devices=n)
    mapped = shard_map(fn, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                       out_specs=P(None, "sp"))
    jitted = jax.jit(mapped)
    blk = jnp.ones((1, 128 * n, 8, 32), jnp.float32)
    out = jitted(blk, blk, blk)                          # compile + warm
    jax.block_until_ready(out)
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(blk, blk, blk))
        times.append(time.perf_counter() - t0)
    measured = sorted(times)[len(times) // 2]
    compiled = jitted.lower(blk, blk, blk).compile()
    _agreement(compiled, "ring", measured, tmp_path)


# ---------------------------------------------------------------------------
# input-bound detection
# ---------------------------------------------------------------------------

def test_input_verdict_unit():
    v = perf.input_verdict(step_s=0.001, io_s=0.009)
    assert v["bound_input"] is True
    assert v["input_share"] == pytest.approx(0.9)
    fast = perf.input_verdict(step_s=0.009, io_s=0.001)
    assert fast["bound_input"] is False
    # histogram-backed path honours the min-sample floor
    telemetry.arm()
    telemetry.observe("data.next_seconds", 0.05)
    assert perf.input_verdict(step_s=0.001) is None      # n=1 < floor
    telemetry.observe("data.next_seconds", 0.05)
    v2 = perf.input_verdict(step_s=0.001)
    assert v2["bound_input"] is True
    assert v2["io_s"] == pytest.approx(0.05, rel=0.01)


class _StarvedIter(DataIter):
    """Tiny in-memory iterator whose fetch is deliberately slower than
    the step it feeds — the SL108 footgun made real."""

    def __init__(self, x, y, batches, delay):
        super().__init__(batch_size=x.shape[0])
        self._x, self._y = x, y
        self._batches, self._delay = batches, delay
        self._i = 0

    def iter_next(self):
        self._i += 1
        return self._i <= self._batches

    def getdata(self):
        time.sleep(self._delay)                # the starved fetch
        return [self._x]

    def getlabel(self):
        return [self._y]

    def getpad(self):
        return 0

    def getindex(self):
        return None


def test_input_starved_run_reads_bound_input(tmp_path, monkeypatch):
    """A toy training loop over a synchronous, slow iterator must come
    out of attribution with the phases verdict ``bound: input`` — the
    runtime twin of srclint's SL108."""
    monkeypatch.setenv("MXNET_TPU_ATTRIBUTION", "1")
    monkeypatch.setenv("MXNET_TPU_ATTRIBUTION_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_ATTRIBUTION_AFTER", "2")
    perf.reset_attributed()
    telemetry.reset()
    telemetry.arm()
    try:
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
        net = mx.sym.SoftmaxOutput(fc, name="softmax")
        tr = ShardedTrainer(net, MeshSpec(make_mesh((1,), ("dp",))),
                            lr=0.1)
        shapes = {"data": (4, 8), "softmax_label": (4,)}
        params, mom, aux = tr.init_state(shapes)
        rs = np.random.RandomState(0)
        x = rs.rand(4, 8).astype(np.float32)
        y = rs.randint(0, 10, 4).astype(np.float32)
        it = _StarvedIter(x, y, batches=4, delay=0.05)
        for batch in it:  # tpulint: disable=SL108  (the point of the test)
            feed = {"data": np.asarray(batch.data[0]),
                    "softmax_label": np.asarray(batch.label[0])}
            params, mom, aux, loss = tr.step(params, mom, aux, feed)
        assert np.isfinite(float(loss))
    finally:
        telemetry.disarm()
    reports = [f for f in os.listdir(str(tmp_path))
               if f.startswith("attribution-")]
    assert len(reports) == 1
    d = json.load(open(os.path.join(str(tmp_path), reports[0])))
    assert d["roofline"]["bound"] == "input"
    assert d["roofline"]["input_share"] > 0.5
    assert d["step"]["io_s"] == pytest.approx(0.05, rel=0.5)
    # the compact form mirrors it: phases_block carries the verdict too
    rep = perf.AttributionReport.load(
        os.path.join(str(tmp_path), reports[0]))
    block = perf.phases_block(rep, "r.json")
    assert block["bound"] == "input"
    assert block["input_share"] > 0.5
    assert "INPUT-BOUND" in rep.pretty()


# ---------------------------------------------------------------------------
# runtime conformance inside attribution + the fleet drill
# ---------------------------------------------------------------------------

def test_attribution_report_carries_conformance(tmp_path, monkeypatch):
    """With a noted pre-flight budget, the attribution report judges the
    measured step against it and exports the per-metric gauge."""
    c = _toy_compiled(64)
    budget = predict.predict_budget(c, "matmul64")
    slow = budget["budget"]["step_time_s"] / 0.4          # 2.5x budget
    telemetry.arm()
    rep = perf.attribute_compiled(c, "matmul64", measured_step_s=slow,
                                  peaks_of=predict.TARGET_DEVICE_KIND)
    d = rep.to_dict()
    conf = d["conformance"]
    assert conf["verdict"] == "VIOLATED"
    assert conf["metrics"]["step_time_s"]["ratio"] == pytest.approx(
        2.5, rel=0.01)
    assert conf["budget_program"] == "matmul64"
    g = telemetry.gauge("perf.conformance")
    assert g.value(entry="matmul64", metric="step_time_s") \
        == pytest.approx(2.5, rel=0.01)
    assert "conformance vs budget" in rep.pretty()
    counters = rep.perfetto_counters(ts_us=1.0)
    assert any(ev["name"].endswith("/conformance") for ev in counters)
    # ... and the refit fed the measured sample back into the store
    store = predict.load_store()
    assert store["entries"], "refit should have written the store"


def test_fleet_drill_flags_rank_over_budget(monkeypatch):
    """4-rank digest drill: rank 2 runs 1.8x over its own budget while
    every p50 looks alike — only the conformance column fingers it."""
    from tests.test_watchdog import FakeKVClient
    telemetry.arm()
    client = FakeKVClient()
    lane = watchdog.HeartbeatLane(client=client)
    monkeypatch.setattr(watchdog, "_LANE", lane)
    now = time.time()
    for rank in range(4):
        conf = {"ratio": 1.02, "verdict": "WITHIN",
                "metric": "step_time_s", "program": "trainer"}
        if rank == 2:
            conf = {"ratio": 1.8, "verdict": "VIOLATED",
                    "metric": "step_time_s", "program": "trainer"}
        client.kv["mxt_hb/%d" % rank] = "9:%.6f" % now
        client.kv["mxt_md/%d" % rank] = json.dumps(
            {"t": now, "step": 9, "conf": conf,
             "step_ms": {"p50": 12.0, "p95": 14.0, "mean": 12.1, "n": 6}})
    rep = lane.straggler_report()
    st = rep["step_time"]
    assert st["budget_violators"] == ["2"]
    assert st["conformance"]["2"]["verdict"] == "VIOLATED"
    assert st["skew"] == pytest.approx(1.0, rel=0.01)     # p50s agree
    rendered = telemetry.render_fleet(telemetry.fleet_view())
    assert "VIOL x1.80" in rendered
    assert "over budget: rank 2 step_time_s x1.80" in rendered
    assert "WITH x1.02" in rendered


def test_straggler_skew_excludes_low_sample_ranks(monkeypatch):
    """A warming-up rank with 1 slow sample must not skew p50 blame."""
    from tests.test_watchdog import FakeKVClient
    telemetry.arm()
    client = FakeKVClient()
    lane = watchdog.HeartbeatLane(client=client)
    monkeypatch.setattr(watchdog, "_LANE", lane)
    now = time.time()
    for rank, (p50, n) in enumerate([(12.0, 8), (13.0, 8), (480.0, 1)]):
        client.kv["mxt_hb/%d" % rank] = "9:%.6f" % now
        client.kv["mxt_md/%d" % rank] = json.dumps(
            {"t": now, "step": 9,
             "step_ms": {"p50": p50, "p95": p50, "mean": p50, "n": n}})
    st = lane.straggler_report()["step_time"]
    assert st["low_sample_ranks"] == [2]
    assert st["min_samples"] == 3
    assert st["slowest_rank"] == 1                 # rank 2 sat out
    assert st["skew"] < 2
    rendered = telemetry.render_fleet(telemetry.fleet_view())
    assert "skew excludes rank(s) 2" in rendered
    # the floor is tunable
    monkeypatch.setenv("MXNET_TPU_SKEW_MIN_SAMPLES", "1")
    st2 = lane.straggler_report()["step_time"]
    assert "low_sample_ranks" not in st2
    assert st2["slowest_rank"] == 2
