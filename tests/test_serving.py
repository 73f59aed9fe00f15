"""Resilient serving runtime tests (mxnet_tpu/serving/ + deploy.py
topology guard + tools/servebench.py).

Three tiers:
 - synthetic-program units: admission/shedding, deadline accounting,
   batching, breaker, swap/rollback, watchdog forensics — no device in
   the loop, so each behavior is isolated and fast;
 - real-artifact tier: export_compiled -> ServingRuntime end-to-end,
   the topology guard, and every ServedProgram.load negative path
   (truncation, CRC flip, pickle refusal, topology mismatch) asserting
   the exact typed error;
 - e2e: the env-armed chaos serving drill (tests/serving_drill.py,
   kill-and-verify) and the tools/servebench.py smoke.
"""
import ctypes  # noqa: F401  (parity with test_capi style)
import json
import os
import pickle
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.deploy import ServedProgram, TopologyMismatch
from mxnet_tpu.resilience import chaos
from mxnet_tpu.resilience.container import (CorruptContainer,
                                            read_container,
                                            write_container)
from mxnet_tpu.serving import (BROKEN, SERVING, CircuitOpen,
                               DeadlineExceeded, ExecFailed, Overloaded,
                               ServingError, ServingRuntime, SwapFailed)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.reset()


class SynthProgram:
    """Program-like test double: fixed (4, 3) batch, optional latency,
    scaled identity math, call counting."""

    def __init__(self, latency=0.0, scale=1.0, features=3):
        self.input_names = ["data"]
        self.input_shapes = {"data": (4, features)}
        self.input_dtypes = {"data": np.dtype(np.float32)}
        self.output_shapes = [(4, features)]
        self.latency = latency
        self.scale = scale
        self.calls = 0

    def forward(self, data):
        self.calls += 1
        if self.latency:
            time.sleep(self.latency)
        return [data * self.scale]


def _row(value=1.0):
    return np.full((3,), value, np.float32)


def _full(value=1.0):
    return np.full((4, 3), value, np.float32)


# ---------------------------------------------------------------------------
# synthetic units
# ---------------------------------------------------------------------------

def test_single_rows_pack_into_one_batch():
    prog = SynthProgram()
    with ServingRuntime(prog, linger=0.1, default_deadline=5) as rt:
        reqs = [rt.submit(data=_row(i)) for i in range(4)]
        for i, r in enumerate(reqs):
            (out,) = r.result(timeout=5)
            assert out.shape == (1, 3)
            np.testing.assert_allclose(out, i)
    assert prog.calls == 1, "4 single rows must dispatch as ONE batch"


def test_full_batch_and_validation_errors():
    with ServingRuntime(SynthProgram(), default_deadline=5) as rt:
        (out,) = rt.predict(data=_full(2.0))
        assert out.shape == (4, 3)
        with pytest.raises(ServingError, match="missing inputs"):
            rt.submit()
        with pytest.raises(ServingError, match="unknown inputs"):
            rt.submit(data=_row(), bogus=_row())
        with pytest.raises(ServingError, match="shape"):
            rt.submit(data=np.zeros((7,), np.float32))
        with pytest.raises(ServingError, match="at most"):
            rt.submit(data=np.zeros((9, 3), np.float32))


def test_overload_sheds_and_priority_evicts():
    prog = SynthProgram(latency=0.3)
    with ServingRuntime(prog, queue_depth=2, linger=0.001,
                        default_deadline=10) as rt:
        r0 = rt.submit(data=_full())            # occupies the executor
        time.sleep(0.05)                        # let the worker pop r0
        r1 = rt.submit(data=_full(), priority=0)
        r2 = rt.submit(data=_full(), priority=0)
        # higher priority evicts the OLDEST lowest-priority request
        r3 = rt.submit(data=_full(), priority=5)
        with pytest.raises(Overloaded, match="evicted"):
            r1.result(timeout=1)
        # equal priority at a full queue is rejected, not admitted
        with pytest.raises(Overloaded, match="queue full"):
            rt.submit(data=_full(), priority=0)
        for r in (r0, r2, r3):
            r.result(timeout=10)
        assert rt.stats()["shed_overload"] == 2


def test_expired_request_dropped_before_dispatch():
    prog = SynthProgram(latency=0.2)
    with ServingRuntime(prog, linger=0.001, default_deadline=10) as rt:
        r0 = rt.submit(data=_full())            # executor busy 0.2s
        time.sleep(0.05)
        r1 = rt.submit(data=_full(), deadline=0.05)
        with pytest.raises(DeadlineExceeded, match="before"):
            r1.result(timeout=5)
        r0.result(timeout=5)
        time.sleep(0.1)                         # worker drains the queue
        assert prog.calls == 1, "expired request must never hit the device"
        assert rt.stats()["shed_expired"] == 1


def test_late_completion_reported_as_deadline_exceeded():
    prog = SynthProgram(latency=0.15)
    with ServingRuntime(prog, linger=0.001, default_deadline=10) as rt:
        r = rt.submit(data=_full(), deadline=0.05)   # dispatches, too slow
        with pytest.raises(DeadlineExceeded):
            r.result(timeout=5)
    assert prog.calls == 1, "this one DID dispatch; lateness is at delivery"


def test_deadline_closes_batch_before_linger():
    """A lone request's batch closes at its deadline less the margin, far
    ahead of the linger, and is delivered in time.  The margin is the room
    the host has to wake the worker and deliver: the default 5 ms is an idle
    host's, and a busy one answered 0.2 s deadlines late."""
    prog = SynthProgram()
    with ServingRuntime(prog, linger=30.0, default_deadline=60,
                        deadline_margin=2.5) as rt:
        t0 = time.monotonic()
        r = rt.submit(data=_row(), deadline=3.0)
        r.result(timeout=10)            # DeadlineExceeded if delivered late
        elapsed = time.monotonic() - t0
    assert elapsed < 10.0, ("deadline margin must close the batch long "
                            "before the 30s linger (took %.3fs)" % elapsed)


def test_retry_absorbs_transient_exec_error():
    prog = SynthProgram()
    with ServingRuntime(prog, retry_tries=2, retry_backoff=0.001,
                        default_deadline=5) as rt:
        with chaos.inject("exec_error", count=1):
            (out,) = rt.predict(data=_full(3.0))
        np.testing.assert_allclose(out, 3.0)
        assert rt.health() == SERVING
        assert rt.stats()["counters"].get("exec_failures", 0) == 0


def test_circuit_breaker_opens_sheds_and_recovers():
    prog = SynthProgram()
    with ServingRuntime(prog, retry_tries=1, breaker_threshold=2,
                        breaker_cooldown=0.25, linger=0.001,
                        default_deadline=5) as rt:
        with chaos.inject("exec_error", count=2):
            for _ in range(2):
                with pytest.raises(ExecFailed):
                    rt.predict(data=_full())
        assert rt.health() == BROKEN
        with pytest.raises(CircuitOpen):
            rt.submit(data=_full())
        time.sleep(0.3)                          # cooldown -> probe allowed
        rt.predict(data=_full())
        assert rt.health() == SERVING
        breaker = rt.stats()["breaker"]
        assert breaker["opened_total"] == 1
        assert breaker["recovered_total"] == 1


def test_swap_rollback_and_bad_swap():
    with ServingRuntime(SynthProgram(scale=1.0), default_deadline=5) as rt:
        with chaos.inject("bad_swap"):
            with pytest.raises(SwapFailed, match="non-finite"):
                rt.swap(SynthProgram(scale=2.0))
        np.testing.assert_allclose(rt.predict(data=_full())[0], 1.0)
        rt.swap(SynthProgram(scale=2.0))
        np.testing.assert_allclose(rt.predict(data=_full())[0], 2.0)
        rt.rollback()
        np.testing.assert_allclose(rt.predict(data=_full())[0], 1.0)
        with pytest.raises(SwapFailed, match="schema mismatch"):
            rt.swap(SynthProgram(features=5))
        stats = rt.stats()["counters"]
        assert stats["swaps"] == 1
        assert stats["swap_failures"] == 2
        assert stats["rollbacks"] == 1


def test_wedged_executor_writes_watchdog_postmortem(tmp_path):
    prog = SynthProgram(latency=0.4)
    with ServingRuntime(prog, exec_timeout=0.1, watchdog_action="wait",
                        report_dir=str(tmp_path), linger=0.001,
                        default_deadline=10, name="wedge-test") as rt:
        with pytest.raises(DeadlineExceeded):
            rt.predict(data=_full(), deadline=0.2)
        deadline = time.monotonic() + 3.0
        reports = []
        while time.monotonic() < deadline and not reports:
            reports = [f for f in os.listdir(str(tmp_path))
                       if f.startswith("watchdog-postmortem")
                       and f.endswith(".json")]
            time.sleep(0.05)
    assert reports, "wedged dispatch must leave stack-dump forensics"
    with open(str(tmp_path / reports[0])) as f:
        report = json.load(f)
    assert report["tag"] == "wedge-test.execute"
    assert report["action"] == "wait"


def test_runtime_close_fails_queued_requests():
    prog = SynthProgram(latency=0.3)
    rt = ServingRuntime(prog, linger=0.001, default_deadline=10)
    r0 = rt.submit(data=_full())
    time.sleep(0.05)
    r1 = rt.submit(data=_full())
    rt.close()
    with pytest.raises(ServingError, match="closed"):
        r1.result(timeout=1)
    with pytest.raises(ServingError):
        rt.submit(data=_full())
    r0.result(timeout=5)     # in-flight work still completes


# ---------------------------------------------------------------------------
# real-artifact tier
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("served") / "model.mxt")
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=5, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    ex = net.simple_bind(mx.cpu(), data=(4, 3))
    rs = np.random.RandomState(0)
    for a in ex.arg_arrays:
        a[:] = mx.nd.array(rs.normal(0, 0.3, a.shape))
    ex.export_compiled(path, input_names=("data",))
    return path


def test_serving_runtime_matches_direct_forward(artifact):
    direct = ServedProgram.load(artifact)
    batch = np.linspace(-1, 1, 12, dtype=np.float32).reshape(4, 3)
    want = direct.forward(data=batch)[0]
    with ServingRuntime(artifact, linger=0.05, default_deadline=10) as rt:
        reqs = [rt.submit(data=batch[i]) for i in range(4)]
        got = np.concatenate([r.result(timeout=10)[0] for r in reqs])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_artifact_records_topology(artifact):
    _, meta, _ = read_container(artifact)
    import jax
    assert meta["platform"] == jax.default_backend()
    assert meta["device_kind"] == jax.devices()[0].device_kind
    assert meta["device_count"] == len(jax.devices())


def _rewrite_meta(artifact, out_path, mutate):
    arrays, meta, blobs = read_container(artifact)
    meta = dict(meta)
    mutate(meta)
    write_container(out_path, arrays=arrays, meta=meta, blobs=blobs)
    return out_path


def test_topology_mismatch_refused_and_overridable(artifact, tmp_path,
                                                   monkeypatch):
    wrong = _rewrite_meta(
        artifact, str(tmp_path / "wrong.mxt"),
        lambda m: m.update(platform="tpu", device_kind="TPU v9000",
                           device_count=4096,
                           topologies={"tpu|TPU v9000|4096": "executable"}))
    with pytest.raises(TopologyMismatch, match="TPU v9000"):
        ServedProgram.load(wrong)
    monkeypatch.setenv("MXNET_TPU_SERVED_IGNORE_TOPOLOGY", "1")
    ServedProgram.load(wrong)        # expert override: loads (and warns)


def test_legacy_artifact_without_topology_loads_with_warning(
        artifact, tmp_path, caplog):
    legacy = _rewrite_meta(
        artifact, str(tmp_path / "legacy.mxt"),
        lambda m: [m.pop(k, None) for k in
                   ("platform", "device_kind", "device_count",
                    "topologies")])
    import logging
    with caplog.at_level(logging.WARNING):
        ServedProgram.load(legacy)
    assert any("topology metadata" in r.message for r in caplog.records)


def test_load_negative_paths_each_typed(artifact, tmp_path):
    # truncated file -> CorruptContainer before any buffer is touched
    with open(artifact, "rb") as f:
        raw = f.read()
    truncated = str(tmp_path / "truncated.mxt")
    with open(truncated, "wb") as f:
        f.write(raw[:len(raw) // 2])
    with pytest.raises(CorruptContainer):
        ServedProgram.load(truncated)

    # one flipped byte inside a payload buffer -> CRC mismatch
    flipped = bytearray(raw)
    flipped[-20] ^= 0xFF             # inside the executable blob tail
    flipped_path = str(tmp_path / "flipped.mxt")
    with open(flipped_path, "wb") as f:
        f.write(bytes(flipped))
    with pytest.raises(CorruptContainer, match="CRC mismatch"):
        ServedProgram.load(flipped_path)

    # pickle streams are refused outright (no code execution on load)
    pickled = str(tmp_path / "evil.mxt")
    with open(pickled, "wb") as f:
        pickle.dump({"innocent": "model"}, f)
    with pytest.raises(CorruptContainer, match="pickle"):
        ServedProgram.load(pickled)


def test_capi_served_predictor_serving_errors(artifact):
    """Python-side C ABI surface: typed serving errors + health/deadline/
    swap entry points (the ctypes boundary itself is test_capi.py)."""
    from mxnet_tpu import capi
    with pytest.raises(Exception):
        capi.pred_create_served("/nonexistent/model.mxt")
    h = capi.pred_create_served(artifact)
    try:
        assert capi.pred_get_health(h) == 0           # SERVING
        capi.pred_set_input(h, "data", np.zeros(12, np.float32))
        capi.pred_set_deadline(h, 1e-6)
        with pytest.raises(DeadlineExceeded):
            capi.pred_forward(h)
        capi.pred_set_deadline(h, 0)                  # back to default
        capi.pred_forward(h)
        assert capi.pred_get_output_shape(h, 0) == [4, 5]
        with pytest.raises(SwapFailed):
            capi.pred_swap_served(h, "/nonexistent/model.mxt")
        capi.pred_forward(h)                          # old model serving
        # non-served handles reject the serving-only entry points
        nh = capi.ndarray_create_none()
        try:
            with pytest.raises(MXNetError, match="served predictor"):
                capi.pred_get_health(nh)
        finally:
            capi.free_handle(nh)
    finally:
        capi.pred_free(h)


# ---------------------------------------------------------------------------
# e2e: chaos drill + servebench
# ---------------------------------------------------------------------------

def test_chaos_serving_drill_kill_and_verify(tmp_path):
    """Acceptance drill: env-armed slow_exec/exec_error/bad_swap against
    a real artifact under saturating load, then a wedged executor that
    the watchdog must kill (exit 43) leaving forensics."""
    env = dict(os.environ,
               MXNET_TPU_CHAOS="exec_errorx4,slow_execx6,bad_swap",
               MXNET_TPU_CHAOS_SLOW_EXEC_SECONDS="0.08")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "serving_drill.py"),
         str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
    assert r.returncode == 43, \
        "watchdog must abort the wedged server (rc=%s)\n%s\n%s" \
        % (r.returncode, r.stdout, r.stderr)
    verdict_lines = [ln for ln in r.stdout.splitlines()
                     if ln.startswith("DRILL_VERDICT ")]
    assert verdict_lines, r.stdout + r.stderr
    v = json.loads(verdict_lines[0][len("DRILL_VERDICT "):])
    # breaker: opens on consecutive failures, sheds typed, recovers
    assert v["health_after_failures"] == "BROKEN"
    assert v["circuit_shed_typed"] is True
    assert v["probe_ok"] is True
    assert v["health_after_probe"] == "SERVING"
    assert v["breaker_opened_total"] == 1
    assert v["breaker_recovered_total"] == 1
    # saturation: bounded queue, typed shedding, pre-dispatch expiry
    assert v["flood_outcomes"]["Overloaded"] > 0
    assert v["flood_outcomes"]["DeadlineExceeded"] > 0
    assert v["flood_outcomes"]["ok"] > 0
    assert v["queue_depth_max"] <= v["queue_bound"]
    assert v["late_ok"] == 0, "no request may be OK past its deadline"
    # hot swap: bad_swap rejected with zero request impact, clean swap
    # actually changes the model
    assert v["bad_swap_typed"] is True
    assert v["unchanged_after_bad_swap"] is True
    assert v["swap_ok"] is True
    assert v["changed_after_good_swap"] is True
    assert v["bg_failures_during_swaps"] == 0
    # chaos telemetry: every env-armed fault firing was counted — the
    # drill asserts "N injected, N absorbed" instead of grepping logs
    # (MXNET_TPU_CHAOS=exec_errorx4,slow_execx6,bad_swap above)
    assert v["faults_injected"] == {"exec_error": 4, "slow_exec": 6,
                                    "bad_swap": 1}
    # kill-and-verify forensics: post-mortem from the wedged phase
    reports = [f for f in os.listdir(str(tmp_path))
               if f.startswith("watchdog-postmortem")
               and f.endswith(".json")]
    assert reports, "abort must leave a post-mortem"
    with open(str(tmp_path / reports[0])) as f:
        report = json.load(f)
    assert report["tag"] == "drill-wedge.execute"
    assert report["action"] == "abort"


def _run_servebench(args):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "servebench.py"),
         "--json"] + args,
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout)


def test_servebench_smoke():
    rep = _run_servebench(["--duration", "0.5", "--concurrency", "4",
                           "--exec-latency", "0.001"])
    assert rep["requests"] > 0 and rep["ok"] > 0
    assert {"p50_ms", "p95_ms", "p99_ms"} <= set(rep["latency"])
    assert "shed_rate" in rep and "queue_depth_max" in rep
    assert rep["runtime_stats"]["health"] == "SERVING"


@pytest.mark.slow
def test_servebench_sustained_open_loop_sheds_not_queues():
    rep = _run_servebench(["--mode", "open", "--rate", "2000",
                           "--duration", "5", "--queue-depth", "32",
                           "--exec-latency", "0.01", "--deadline", "0.1"])
    assert rep["requests"] > 1000
    assert rep["shed_rate"] > 0, "sustained overload must shed"
    assert rep["queue_depth_max"] <= 32, "queue must stay bounded"
    assert rep["ok"] > 0
