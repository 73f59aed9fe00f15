"""Distributed-tracing tests (mxnet_tpu/telemetry/tracing.py +
serving propagation + tools/tracewatch.py).

Three tiers, like test_fleet.py:
 - unit seams with no processes: context mint/wire round trip, the
   sampling bit, the bounded flight-recorder sink, request-lane
   reconstruction, the tracewatch merge (lanes, flows, orphans), the
   disarmed zero-cost gate, and the compile/ span family;
 - process drills: real replica processes behind the router with
   tracing armed — THE kill drill (chaos ``replica_crash`` SIGKILLs a
   replica mid-batch under load: evict + re-dispatch under ONE
   trace_id, zero orphan spans, merge passes the existing
   trace-nesting validity helper) and the hedge drill (winner ok,
   loser marked cancelled, hedge events in fleet-events.jsonl with
   trace ids);
 - tenant SLO: the flooding tenant burns only its own budget —
   router stats table, registry mirror, render_fleet table.
"""
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serving import TenantPolicy
from mxnet_tpu.serving.errors import Cancelled, DeadlineExceeded
from mxnet_tpu.serving.fleet import ServingFleet
from mxnet_tpu.serving.request import Request
from mxnet_tpu.telemetry import tracing

from test_telemetry import _check_nesting

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tracewatch():
    spec = importlib.util.spec_from_file_location(
        "tracewatch", os.path.join(REPO, "tools", "tracewatch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tracewatch = _load_tracewatch()


@pytest.fixture(autouse=True)
def _clean():
    chaos.reset()
    telemetry.reset()          # clears tracing arm state + cached sink
    yield
    chaos.reset()
    telemetry.reset()


def _settled_request(trace=None, error=None, popped=True, exec_done=True):
    req = Request({"data": None}, 2, priority=1,
                  deadline=time.monotonic() + 60.0)
    # phase timestamps sit slightly in the PAST so the settle time the
    # one-shot future stamps (now) bounds them all
    now = time.monotonic() - 0.01
    if popped:
        req.t_popped = now
        req.t_dispatched = now + 0.001
        req.batch_seq = 7
    if exec_done:
        req.t_exec_done = now + 0.004
    req.trace = trace
    if error is None:
        req._deliver([])
    else:
        req._fail(error)
    return req


def _sink_spans(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# unit seams
# ---------------------------------------------------------------------------

def test_context_mint_wire_roundtrip_and_sampling(tmp_path):
    assert tracing.new_context() is None          # disarmed: no work
    tracing.arm(sample=1.0)
    tracing.set_sink_dir(str(tmp_path))
    ctx = tracing.new_context()
    assert ctx is not None and ctx.sampled and ctx.parent_id is None
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.parent_id == ctx.span_id
    # wire round trip: the sender's span id becomes the receiver's
    # PARENT (W3C-traceparent discipline) under a fresh local span id
    rebound = tracing.from_wire(child.to_wire())
    assert rebound.trace_id == ctx.trace_id
    assert rebound.parent_id == child.span_id
    assert rebound.span_id != child.span_id
    assert rebound.sampled
    # garbage on the wire is tolerated, never fatal
    assert tracing.from_wire(None) is None
    assert tracing.from_wire({"tid": "x"}) is None
    assert tracing.from_wire("nonsense") is None

    # unsampled: ids still mint (event logs stay correlatable), spans
    # do not record
    tracing.arm(sample=0.0)
    ctx0 = tracing.new_context()
    assert ctx0 is not None and not ctx0.sampled
    assert tracing.record("x", ctx0, time.time(), 0.1) is None
    assert tracing.from_wire(ctx0.child().to_wire()).sampled is False


def test_sink_is_bounded_flight_recorder(tmp_path):
    path = str(tmp_path / "trace-t-1.jsonl")
    sink = tracing.TraceSink(path, max_spans=20)
    for i in range(95):
        sink.append({"trace": "t", "span": "s%d" % i, "name": "n"})
    sink.close()
    spans = _sink_spans(path)
    assert len(spans) <= 20                     # hard bound held
    assert spans[-1]["span"] == "s94"           # newest survive
    assert int(spans[0]["span"][1:]) > 0        # oldest compacted away


def test_record_served_request_reconstructs_lanes(tmp_path):
    tracing.arm(sample=1.0)
    tracing.set_sink_dir(str(tmp_path))
    tracing.set_process_label("replica9")
    wirectx = tracing.from_wire(
        {"tid": "t" * 16, "sid": "d" * 16, "smp": 1})
    tracing.record_served_request(_settled_request(trace=wirectx))
    spans = _sink_spans(tracing.sink_path())
    by_name = {s["name"]: s for s in spans}
    root = by_name["replica/request"]
    assert root["parent"] == "d" * 16            # the dispatch span
    assert root["outcome"] == "ok"
    assert root["attrs"]["batch"] == 7           # executor batch seq
    for phase in ("serve/queue_wait", "serve/batch_fill", "serve/exec",
                  "serve/deliver"):
        assert by_name[phase]["parent"] == root["span"]
        assert by_name[phase]["proc"] == "replica9"

    # a request with no trace records nothing; outcomes map typed errors
    tracing.record_served_request(_settled_request(trace=None))
    assert len(_sink_spans(tracing.sink_path())) == len(spans)
    tracing.record_served_request(_settled_request(
        trace=wirectx.child(), error=Cancelled("hedge lost"),
        exec_done=False))
    cancelled = [s for s in _sink_spans(tracing.sink_path())
                 if s["outcome"] == "cancelled"]
    assert cancelled and any(s["name"] == "replica/request"
                             for s in cancelled)


def test_request_outcome_vocabulary():
    assert tracing.request_outcome(_settled_request()) == "ok"
    assert tracing.request_outcome(
        _settled_request(error=Cancelled("x"))) == "cancelled"
    assert tracing.request_outcome(
        _settled_request(error=DeadlineExceeded("x"))) == "deadline"
    assert tracing.request_outcome(
        _settled_request(error=RuntimeError("x"))) == "error:RuntimeError"


def test_bind_donates_ordinary_spans_to_the_trace(tmp_path):
    tracing.arm(sample=1.0)
    tracing.set_sink_dir(str(tmp_path))
    ctx = tracing.new_context()
    with tracing.bind(ctx):
        with telemetry.span("work/inner", cat="test", step=3):
            pass
    with telemetry.span("work/outside", cat="test"):
        pass                                     # unbound: not recorded
    spans = _sink_spans(tracing.sink_path())
    names = [s["name"] for s in spans]
    assert "work/inner" in names and "work/outside" not in names
    inner = next(s for s in spans if s["name"] == "work/inner")
    assert inner["trace"] == ctx.trace_id
    assert inner["parent"] == ctx.span_id


def test_disarmed_gates_are_zero_cost():
    """The tracing gates the serving hot path gained (context mint at
    submit, request-lane emission at settle) must stay inside the
    telemetry layer's disarmed per-call bound."""
    req = _settled_request(trace=None)
    n = 3000
    t0 = time.perf_counter()
    for i in range(n):
        tracing.new_context()
        tracing.record_served_request(req)
        with telemetry.span("t/hot", step=i):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 50e-6, "disarmed tracing cost %.1fus" % (
        per_call * 1e6)
    assert tracing.sink_path() is None          # nothing ever opened


def test_tracewatch_merge_lanes_flows_and_orphans(tmp_path):
    """Two synthetic process sinks -> one merged Perfetto trace: the
    existing nesting validity helper passes, cross-process edges get
    flow events, hedged (overlapping) dispatches land on sibling lanes,
    and a parentless span is flagged as an orphan."""
    t0 = 1000.0

    def rec(trace, span, parent, name, pid, proc, a, b, outcome="ok"):
        return {"trace": trace, "span": span, "parent": parent,
                "name": name, "cat": "t", "pid": pid, "proc": proc,
                "t0": t0 + a, "dur": b - a, "outcome": outcome}

    router = [
        rec("T1", "R1", None, "fleet/request", 1, "router", 0.0, 0.100),
        # two OVERLAPPING dispatches (a hedge): must fan out onto
        # sibling lanes, not overlap on one
        rec("T1", "D1", "R1", "fleet/dispatch", 1, "router", 0.001,
            0.095, outcome="cancelled"),
        rec("T1", "D2", "R1", "fleet/dispatch", 1, "router", 0.050,
            0.099),
    ]
    replica = [
        rec("T1", "S1", "D2", "replica/request", 2, "replica0", 0.052,
            0.090),
        rec("T1", "S2", "S1", "serve/exec", 2, "replica0", 0.053, 0.089),
    ]
    with open(tmp_path / "trace-router-1.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in router)
    with open(tmp_path / "trace-replica0-2.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in replica)

    spans, bad = tracewatch.load_spans([str(tmp_path)])
    assert bad == 0 and len(spans) == 5
    assert tracewatch.find_orphans(spans) == []
    trace = tracewatch.merge_trace(spans)
    events = trace["traceEvents"]
    _check_nesting([e for e in events if e["ph"] == "X"])
    xs = {e["args"]["span"]: e for e in events if e["ph"] == "X"}
    assert xs["D1"]["tid"] != xs["D2"]["tid"]       # hedge fan-out
    assert xs["S1"]["pid"] != xs["D2"]["pid"]
    flows = [e for e in events if e["ph"] in ("s", "f")]
    assert len(flows) >= 2                          # D2 -> S1 at least
    procs = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"router", "replica0"} <= procs

    # text rendering of one request
    import io
    buf = io.StringIO()
    tracewatch.render_request(spans, "T1", out=buf)
    text = buf.getvalue()
    assert "fleet/request" in text and "replica/request" in text
    assert "cancelled" in text

    # an orphan (parent never recorded anywhere) is flagged
    with open(tmp_path / "trace-ghost-3.jsonl", "w") as f:
        f.write(json.dumps(rec("T1", "X1", "NOPE", "serve/exec", 3,
                               "ghost", 0.01, 0.02)) + "\n")
    spans2, _ = tracewatch.load_spans([str(tmp_path)])
    orphans = tracewatch.find_orphans(spans2)
    assert [s["span"] for s in orphans] == ["X1"]
    assert tracewatch.main([str(tmp_path), "--check",
                            "--out", str(tmp_path / "m.json")]) == 1


def test_compile_span_family_trainer_first_step():
    """ROADMAP item 5 prep: the trainer's first-step jit compile lands
    in the compile.seconds registry histogram and the always-on
    compile_summary() the bench ledger extra reads."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    telemetry.arm()
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc1")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    trainer = ShardedTrainer(net, MeshSpec(make_mesh((1,), ("dp",))))
    shapes = {"data": (4, 3), "softmax_label": (4,)}
    params, mom, aux = trainer.init_state(shapes)
    rs = np.random.RandomState(0)
    batch = {"data": rs.rand(4, 3).astype(np.float32),
             "softmax_label": rs.randint(0, 2, 4).astype(np.float32)}
    before = tracing.compile_summary()["count"]
    for _ in range(2):
        params, mom, aux, loss = trainer.step(params, mom, aux, batch)
    summary = tracing.compile_summary()
    assert summary["count"] == before + 1          # compiled ONCE
    assert summary["by_name"].get("train_step", 0) > 0
    assert summary["total_seconds"] > 0
    hist = telemetry.histogram("compile.seconds").summary(
        what="train_step")
    assert hist["count"] >= 1 and hist["sum"] > 0


# ---------------------------------------------------------------------------
# process drills
# ---------------------------------------------------------------------------

def _mk_traced_fleet(n, tmp_path, monkeypatch, latency=0.005, **kw):
    monkeypatch.setenv("MXNET_TPU_TRACE", "1")
    tracing.reset()            # re-read the env in THIS (router) process
    kw.setdefault("synthetic", (4, 3, latency))
    kw.setdefault("fleet_dir", str(tmp_path / "fleet"))
    kw.setdefault("stale_after", 0.8)
    kw.setdefault("scan_interval", 0.05)
    kw.setdefault("ready_timeout", 45.0)
    return ServingFleet(n, **kw)


def _events(fleet):
    path = os.path.join(fleet.fleet_dir, "fleet-events.jsonl")
    if not os.path.isfile(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _merged_ok(fleet_dir):
    """Load all sinks, assert zero orphans + nesting validity; returns
    the spans.  A SIGKILLed replica may leave at most one partial
    line (killed mid-append) — tolerated, like the loader itself does."""
    spans, bad = tracewatch.load_spans([fleet_dir])
    assert bad <= 1, "unreadable sink lines: %d" % bad
    assert spans, "no trace spans recorded"
    orphans = tracewatch.find_orphans(spans)
    assert orphans == [], "orphan spans: %r" % orphans[:5]
    events = tracewatch.merge_trace(spans)["traceEvents"]
    _check_nesting([e for e in events if e["ph"] == "X"])
    # cross-process parent/child edges became flow links
    assert any(e["ph"] == "s" for e in events)
    return spans


def test_trace_kill_drill_one_trace_zero_orphans(tmp_path, monkeypatch):
    """THE acceptance drill, traced: chaos ``replica_crash`` SIGKILLs a
    replica mid-batch under load.  The merged trace shows the evicted
    dispatch AND its re-dispatch under ONE trace_id across >= 3
    processes, with zero orphan spans and valid nesting."""
    fleet = _mk_traced_fleet(
        3, tmp_path, monkeypatch, latency=0.01,
        replica_env={1: {"MXNET_TPU_CHAOS": "replica_crash@15"}})
    try:
        deadline = 1.5
        errs = {}
        lock = threading.Lock()
        stop_at = time.monotonic() + 2.5
        x = np.full((3,), 1.0, np.float32)

        def worker():
            while time.monotonic() < stop_at:
                try:
                    req = fleet.submit(data=x, deadline=deadline)
                    req.result(timeout=deadline + 5.0)
                except Exception as e:
                    with lock:
                        k = type(e).__name__
                        errs[k] = errs.get(k, 0) + 1

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not errs, "requests failed during the kill drill: %s" % errs
        c = fleet.stats()["counters"]
        assert c["evictions"] >= 1
        events = _events(fleet)
    finally:
        fleet.close()

    spans = _merged_ok(fleet.fleet_dir)
    procs = {s["proc"] for s in spans}
    assert len(procs) >= 4, procs        # router + 3 replicas
    # the re-dispatch events carry trace ids that resolve to real trees
    redis = [e for e in events if e["event"] == "redispatch"]
    assert redis, "no redispatch events in fleet-events.jsonl"
    traced = [e for e in redis if e.get("trace")]
    assert traced, "redispatch events lost their trace ids"
    tid = traced[0]["trace"]
    mine = [s for s in spans if s["trace"] == tid]
    dispatches = [s for s in mine if s["name"] == "fleet/dispatch"]
    assert len(dispatches) >= 2, \
        "re-dispatched request shows %d dispatch spans" % len(dispatches)
    outcomes = {s["outcome"] for s in dispatches}
    assert "ok" in outcomes and outcomes - {"ok"}, outcomes
    roots = [s for s in mine if s["name"] == "fleet/request"]
    assert len(roots) == 1 and roots[0]["outcome"] == "ok"
    # every span of this request's story is under the ONE trace id
    assert all(s["trace"] == tid for s in mine)


def test_trace_hedge_winner_and_cancelled_loser(tmp_path, monkeypatch):
    """Hedge drill, traced: the straggler replica's copy is marked
    cancelled on BOTH sides (router dispatch span + replica request
    span), the winner is ok, and the hedge/cancel events carry the
    trace id into fleet-events.jsonl and postmortem --fleet."""
    # hedge_factor 0.1, as in test_fleet's two drills of a replica that lags
    # every batch: the hedge delay stays at hedge_min whatever p95 the
    # straggler has published by the time the requests go out
    fleet = _mk_traced_fleet(
        2, tmp_path, monkeypatch, latency=0.005,
        hedge_min=0.05, hedge_factor=0.1,
        replica_env={1: {"MXNET_TPU_CHAOS": "hedge_lagx1000000",
                         "MXNET_TPU_CHAOS_HEDGE_LAG_SECONDS": "0.4"}})
    try:
        x = np.full((3,), 1.0, np.float32)
        for _ in range(12):
            fleet.predict(data=x, deadline=10.0)
        c = fleet.stats()["counters"]
        assert c.get("hedge_won", 0) >= 1, c
        time.sleep(0.4)        # let cancelled losers settle replica-side
        events = _events(fleet)
    finally:
        fleet.close()

    spans = _merged_ok(fleet.fleet_dir)
    hedged = [s for s in spans if s["name"] == "fleet/dispatch"
              and (s.get("attrs") or {}).get("hedge")]
    assert hedged, "no hedge dispatch spans"
    tid = hedged[0]["trace"]
    mine = [s for s in spans if s["trace"] == tid]
    d_out = {s["outcome"] for s in mine if s["name"] == "fleet/dispatch"}
    assert d_out == {"ok", "cancelled"}, d_out
    # the loser is cancelled on the REPLICA side too — both copies'
    # request spans are present in the merged trace
    rep_out = {s["outcome"] for s in mine
               if s["name"] == "replica/request"}
    assert "cancelled" in rep_out, rep_out
    assert "ok" in rep_out, rep_out
    # events carry the trace id; all three previously-missing kinds land
    kinds = {e["event"] for e in events}
    assert {"hedge_fired", "hedge_won", "cancelled"} <= kinds, kinds
    for e in events:
        if e["event"] in ("hedge_fired", "hedge_won", "cancelled"):
            assert e.get("trace"), e

    # postmortem --fleet renders the hedge timeline with trace ids
    import subprocess, sys
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "postmortem.py"),
         "--fleet", fleet.fleet_dir],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0
    assert "hedge_fired" in out.stdout and "hedge_won" in out.stdout
    assert "trace=" in out.stdout


def test_tenant_slo_flood_burns_only_its_own_budget(tmp_path,
                                                    monkeypatch):
    """Per-tenant SLO accounting: a flooding tenant's sheds and budget
    burn stay on its own row; the vip tenant keeps availability 1.0 —
    in router.stats(), in the registry mirror, and in render_fleet()'s
    tenant table via the router's lane digest."""
    telemetry.arm()
    fleet = _mk_traced_fleet(
        2, tmp_path, monkeypatch, latency=0.002,
        quotas={"flood": TenantPolicy(rate=25, burst=4, priority=0),
                "vip": TenantPolicy(priority=5)})
    try:
        x = np.full((3,), 1.0, np.float32)
        stop_at = time.monotonic() + 1.6

        def flooder():
            while time.monotonic() < stop_at:
                try:
                    fleet.predict(data=x, tenant="flood", deadline=1.0)
                except Exception:
                    time.sleep(0.002)

        threads = [threading.Thread(target=flooder, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        vip_ok = 0
        while time.monotonic() < stop_at:
            fleet.predict(data=x, tenant="vip", deadline=1.0)
            vip_ok += 1
            time.sleep(0.02)
        for t in threads:
            t.join(timeout=10.0)
        time.sleep(0.7)                    # let the router publish SLO
        tenants = fleet.stats()["tenants"]

        assert vip_ok >= 20
        assert tenants["flood"]["shed"].get("quota", 0) > 0
        assert tenants["vip"]["shed"] == {}
        assert tenants["vip"]["availability"] == 1.0
        assert tenants["vip"]["ok"] == vip_ok
        assert "latency_ms" in tenants["vip"]
        assert tenants["flood"]["budget_burn"]["p95"] < 1.0

        # registry mirror carries tenant labels
        shed = telemetry.counter("fleet.tenant.shed")
        assert shed.value(cause="quota", tenant="flood") > 0
        assert shed.value(cause="quota", tenant="vip") == 0

        # render_fleet() shows the tenant table from the lane digest
        monkeypatch.setenv("MXNET_TPU_FLEET_DIR", fleet.fleet_dir)
        text = telemetry.render_fleet(
            telemetry.serving_fleet_view(fleet.fleet_dir))
        assert "tenant SLO" in text
        assert "flood" in text and "vip" in text
    finally:
        fleet.close()
