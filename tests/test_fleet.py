"""Serving-fleet tests (mxnet_tpu/serving/{wire,replica,router,fleet}.py
+ the FileKVClient lane + chaos replica_crash/hedge_lag + tools).

Three tiers, like test_serving.py:
 - protocol/unit seams with no processes: wire framing, the file-backed
   coordination-KV lane, tenant token buckets, replica digests, fleet
   rendering, cancelled-request queue behavior;
 - process drills: real replica processes behind the router — the
   kill-one-replica acceptance drill (chaos ``replica_crash`` SIGKILLs a
   replica MID-BATCH; zero late OKs, in-flight requests complete via
   hedging/re-dispatch, eject + relaunch + re-admit), the hedge_lag
   straggler drill, tenant fairness, priority-eviction parity with the
   PR-4 in-replica semantics, and the rolling swap with fleet-wide
   rollback on a failing canary;
 - tools: servebench --replicas smoke (+ @slow sustained kill drill) and
   postmortem --fleet rendering; @slow 1->4 replica QPS scaling.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mxnet_tpu.resilience import chaos
from mxnet_tpu.resilience.watchdog import FileKVClient, HeartbeatLane
from mxnet_tpu.serving import (Overloaded, QuotaExceeded, ServingRuntime,
                               SwapFailed, TenantPolicy)
from mxnet_tpu.serving import wire
from mxnet_tpu.serving.admission import AdmissionQueue
from mxnet_tpu.serving.errors import Cancelled
from mxnet_tpu.serving.fleet import ServingFleet, fleet_lane
from mxnet_tpu.serving.replica import SyntheticProgram, _schema_of
from mxnet_tpu.serving.request import Request
from mxnet_tpu.telemetry import render_fleet, replica_digest, \
    serving_fleet_view

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.reset()


def _row(value=1.0, features=3):
    return np.full((features,), value, np.float32)


def _mk_fleet(n, tmp_path, latency=0.005, **kw):
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


# ---------------------------------------------------------------------------
# protocol / unit seams (no processes)
# ---------------------------------------------------------------------------

def test_wire_roundtrip_and_framing_errors():
    a, b = socket.socketpair()
    try:
        arrays = {"data": np.arange(12, dtype=np.float32).reshape(3, 4),
                  "mask": np.array([1, 0, 1], np.int8)}
        wire.send_msg(a, {"op": "submit", "id": 7, "deadline": 0.5},
                      arrays)
        header, got = wire.recv_msg(b)
        assert header["op"] == "submit" and header["id"] == 7
        assert set(got) == {"data", "mask"}
        np.testing.assert_array_equal(got["data"], arrays["data"])
        np.testing.assert_array_equal(got["mask"], arrays["mask"])
        assert got["data"].dtype == np.float32

        # empty-array and no-array frames round-trip too
        wire.send_msg(a, {"op": "ping"},
                      {"empty": np.zeros((0, 4), np.float32)})
        header, got = wire.recv_msg(b)
        assert got["empty"].shape == (0, 4)

        # garbage magic is a typed WireError, not a hang or a crash
        a.sendall(b"GARBAGE-NOT-A-FRAME!")
        with pytest.raises(wire.WireError):
            wire.recv_msg(b)
    finally:
        a.close()
        b.close()

    # an absurd payload_len is refused BEFORE any allocation: a corrupt
    # frame must not be able to force a multi-GB buffer into existence
    a, b = socket.socketpair()
    try:
        a.sendall(wire._FIXED.pack(wire.MAGIC, 2, (1 << 62)) + b"{}")
        with pytest.raises(wire.WireError, match="payload length"):
            wire.recv_msg(b)
    finally:
        a.close()
        b.close()


def test_file_kv_client_and_lane(tmp_path):
    kv = FileKVClient(str(tmp_path / "kv"))
    kv.key_value_set("mxt_hb/0", "1:2.0:0")
    kv.key_value_set("mxt_hb/1", "5:3.0:0")
    kv.key_value_set("other/9", "zzz")
    got = dict(kv.key_value_dir_get("mxt_hb/"))
    assert got == {"mxt_hb/0": "1:2.0:0", "mxt_hb/1": "5:3.0:0"}
    # overwrite-in-place and delete
    kv.key_value_set("mxt_hb/0", "2:4.0:0")
    assert kv.key_value_get("mxt_hb/0") == "2:4.0:0"
    kv.key_value_delete("mxt_hb/1")
    assert dict(kv.key_value_dir_get("mxt_hb/")) == {"mxt_hb/0": "2:4.0:0"}

    # the PR-5 HeartbeatLane runs unchanged over the file client, with
    # an explicit rank and an explicit (serving) digest
    lane = HeartbeatLane(client=kv, rank=3)
    assert lane.beat(17, force=True, digest={"kind": "serving", "x": 1})
    peers = lane.peers()
    assert peers[3]["step"] == 17
    assert lane.digests()[3] == {"kind": "serving", "x": 1}
    lane.evict(3)
    assert 3 not in lane.peers() and 3 not in lane.digests()


def test_tenant_policy_token_bucket():
    pol = TenantPolicy(rate=10, burst=3)
    t0 = 1000.0
    # burst drains first
    assert [pol.try_acquire(now=t0) for _ in range(4)] == \
        [True, True, True, False]
    # 0.25s at 10/s refills 2.5 tokens -> exactly 2 more admits
    assert pol.try_acquire(now=t0 + 0.25)
    assert pol.try_acquire(now=t0 + 0.25)
    assert not pol.try_acquire(now=t0 + 0.25)
    # unlimited tenant never sheds
    assert all(TenantPolicy().try_acquire() for _ in range(100))


def test_replica_digest_carries_router_facts():
    prog = SyntheticProgram(4, 3, 0.0)
    with ServingRuntime(prog, name="digest-test") as rt:
        rt.predict({"data": _row()}, deadline=2.0)
        d = replica_digest(rt, 2, port=4567, qps=12.5, model="v1",
                           schema=_schema_of(prog))
    assert d["kind"] == "serving" and d["replica"] == 2
    assert d["port"] == 4567 and d["qps"] == 12.5
    assert d["health"] == "SERVING" and d["pid"] == os.getpid()
    assert d["schema"]["input_names"] == ["data"]
    assert d["schema"]["input_shapes"]["data"] == [4, 3]
    assert "p95" in d["lat_ms"]
    assert d["counters"]["completed"] == 1


def test_serving_fleet_view_and_render(tmp_path, monkeypatch):
    fleet_dir = str(tmp_path / "f")
    prog = SyntheticProgram(4, 3, 0.0)
    with ServingRuntime(prog, name="view-test") as rt:
        rt.predict({"data": _row()}, deadline=2.0)
        for rid in (0, 1):
            lane = fleet_lane(fleet_dir, rank=rid)
            lane.beat(3, force=True,
                      digest=replica_digest(rt, rid, port=1000 + rid,
                                            qps=5.0,
                                            schema=_schema_of(prog)))
    view = serving_fleet_view(fleet_dir)
    assert set(view["replicas"]) == {"0", "1"}
    assert view["replicas"]["0"]["digest"]["port"] == 1000
    rendered = render_fleet(view)
    assert "serving replicas" in rendered
    assert "SERVING" in rendered
    # and the combined training fleet_view picks the serving table up
    # from MXNET_TPU_FLEET_DIR, rendering both planes in one call
    monkeypatch.setenv("MXNET_TPU_FLEET_DIR", fleet_dir)
    from mxnet_tpu.telemetry import fleet_view
    combined = fleet_view()
    assert set(combined["serving"]["replicas"]) == {"0", "1"}
    assert "serving replicas" in render_fleet(combined)


def test_admission_queue_skips_cancelled_requests():
    q = AdmissionQueue(4)
    live = Request({"data": _row()[None]}, 1, seq=1)
    dead = Request({"data": _row()[None]}, 1, seq=2)
    q.offer(dead)
    q.offer(live)
    dead._fail(Cancelled("hedge won elsewhere"))
    got = q.pop_live(timeout=0.1)
    assert got is live                 # the cancelled one was dropped
    assert q.pop_live(timeout=0.01) is None
    # and the cancellation did not count as an expiry shed
    assert q.shed_expired == 0


# ---------------------------------------------------------------------------
# process drills
# ---------------------------------------------------------------------------

def test_fleet_kill_replica_drill(tmp_path):
    """THE acceptance drill: chaos ``replica_crash`` SIGKILLs replica 1
    mid-batch under sustained load.  Zero late OKs, zero failed
    requests (in-flight ones complete elsewhere via hedging/re-dispatch
    within their deadlines), the router ejects the dead replica, the
    supervisor relaunches it, and the router re-admits it."""
    fleet = _mk_fleet(
        3, tmp_path, latency=0.01,
        replica_env={1: {"MXNET_TPU_CHAOS": "replica_crash@15"}})
    try:
        deadline = 1.5
        results = {"ok": 0, "late": 0, "err": {}}
        lock = threading.Lock()
        stop_at = time.monotonic() + 2.5
        x = _row()

        def worker():
            while time.monotonic() < stop_at:
                t0 = time.monotonic()
                try:
                    req = fleet.submit(data=x, deadline=deadline)
                    req.result(timeout=deadline + 5.0)
                    lat = time.monotonic() - t0
                    with lock:
                        if lat > deadline + 0.05:
                            results["late"] += 1
                        else:
                            results["ok"] += 1
                except Exception as e:
                    with lock:
                        k = type(e).__name__
                        results["err"][k] = results["err"].get(k, 0) + 1

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)

        assert results["late"] == 0, "late OK delivered: %s" % results
        assert not results["err"], \
            "requests failed during the kill drill: %s" % results
        assert results["ok"] > 50
        c = fleet.stats()["counters"]
        assert c["evictions"] >= 1
        # the in-flight requests of the dead replica completed elsewhere
        assert c.get("redispatched", 0) + c.get("hedge_fired", 0) >= 1
        # relaunch + re-admit: all three slots READY again (the crashed
        # replica only re-arms its chaos once traffic resumes, and the
        # load is over)
        assert fleet.router.wait_ready(3, timeout=20.0), \
            fleet.router.replicas()
        events = [e["event"] for e in _events(fleet)]
        assert "evict" in events and "readmit" in events
    finally:
        fleet.close()


# for the two drills of a replica that lags every batch: see the first
_HEDGE_FACTOR = 0.1


def test_fleet_hedging_bounds_straggler_tail(tmp_path):
    """chaos ``hedge_lag`` turns replica 1 into a persistent 0.4s
    straggler.  The router's digest-informed hedging keeps every request
    inside a small multiple of the healthy replica's latency — no
    request ever waits out the full lag."""
    # the hedge delay is the TARGET's own published p95 x hedge_factor, so
    # at 1.5 a straggler whose digest already holds its 0.4 s lag (a slow
    # start on a busy host) is hedged at 0.6 s: never.  0.1 keeps the delay
    # at hedge_min whatever has been published (_HEDGE_FACTOR below)
    fleet = _mk_fleet(
        2, tmp_path, latency=0.005,
        hedge_min=0.05, hedge_factor=_HEDGE_FACTOR,
        replica_env={1: {"MXNET_TPU_CHAOS": "hedge_lagx1000000",
                         "MXNET_TPU_CHAOS_HEDGE_LAG_SECONDS": "0.4"}})
    try:
        lat = []
        x = _row()
        for _ in range(30):
            t0 = time.monotonic()
            fleet.predict(data=x, deadline=2.0)
            lat.append(time.monotonic() - t0)
        c = fleet.stats()["counters"]
        assert c["ok"] == 30
        assert c.get("hedge_fired", 0) >= 1, c
        # every request that landed on the straggler was rescued by its
        # hedge far below the 0.4s lag
        assert max(lat) < 0.3, "tail not bounded: max=%.3fs" % max(lat)
    finally:
        fleet.close()


def test_hedge_losers_are_reaped_and_fleet_still_swaps(tmp_path):
    """Regression: a cancelled hedge loser gets no reply from the
    replica, so the router must reap its bookkeeping itself in _finish.
    Before the fix, one won hedge left the loser's ``inflight`` pinned
    at 1 forever — skewing least-loaded dispatch and wedging
    ``swap_fleet`` (whose drain waits for inflight == 0)."""
    fleet = _mk_fleet(
        2, tmp_path, latency=0.005,
        hedge_min=0.05, hedge_factor=_HEDGE_FACTOR,
        replica_env={1: {"MXNET_TPU_CHAOS": "hedge_lagx1000000",
                         "MXNET_TPU_CHAOS_HEDGE_LAG_SECONDS": "0.4"}})
    try:
        x = _row()
        for _ in range(10):
            fleet.predict(data=x, deadline=10.0)
        c = fleet.stats()["counters"]
        assert c.get("hedge_won", 0) >= 1, c   # losers actually existed
        # every loser's inflight must have been reaped at finish time,
        # not parked waiting for a cancel reply that never comes
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            inflight = {rid: r["inflight"]
                        for rid, r in fleet.stats()["replicas"].items()}
            if all(n == 0 for n in inflight.values()):
                break
            time.sleep(0.02)
        assert all(n == 0 for n in inflight.values()), \
            "leaked inflight after won hedges: %r" % inflight
        # and the drain-gated rolling swap still completes
        swapped = fleet.swap({"batch": 4, "features": 3, "scale": 3.0},
                             tag="post-hedge")
        assert len(swapped) == 2
        out = fleet.predict(data=x, deadline=10.0)
        np.testing.assert_allclose(out[0][0], 3.0 * x, rtol=1e-6)
    finally:
        fleet.close()


def test_tenant_fairness_quota_and_priority(tmp_path):
    """A flooding low-priority tenant is shed at ITS quota with
    QuotaExceeded while a low-QPS high-priority tenant keeps its p99 —
    nobody else pays for the flood."""
    fleet = _mk_fleet(
        2, tmp_path, latency=0.002,
        quotas={"flood": TenantPolicy(rate=30, burst=5, priority=0),
                "vip": TenantPolicy(priority=5)})
    try:
        x = _row()
        stats = {"flood_ok": 0, "shed": 0, "vip_ok": 0, "other": {}}
        vip_lat = []
        lock = threading.Lock()
        stop_at = time.monotonic() + 2.5

        def flooder():
            while time.monotonic() < stop_at:
                try:
                    fleet.predict(data=x, tenant="flood", deadline=1.0)
                    with lock:
                        stats["flood_ok"] += 1
                except QuotaExceeded:
                    with lock:
                        stats["shed"] += 1
                    time.sleep(0.002)      # paced flood, not a spin
                except Exception as e:
                    with lock:
                        k = type(e).__name__
                        stats["other"][k] = stats["other"].get(k, 0) + 1

        threads = [threading.Thread(target=flooder, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        while time.monotonic() < stop_at:
            t0 = time.monotonic()
            fleet.predict(data=x, tenant="vip", deadline=1.0)
            vip_lat.append(time.monotonic() - t0)
            with lock:
                stats["vip_ok"] += 1
            time.sleep(0.02)
        for t in threads:
            t.join(timeout=10.0)

        assert stats["shed"] > 0, stats
        assert not stats["other"], stats
        # the flood got through at ~its token rate (30/s for 2.5s +
        # burst), not at its attempt rate
        assert stats["flood_ok"] <= 30 * 2.5 + 5 + 10, stats
        assert stats["vip_ok"] >= 50
        vip_lat.sort()
        p99 = vip_lat[max(0, int(len(vip_lat) * 0.99) - 1)]
        assert p99 < 0.5, "vip p99 %.3fs collateral from the flood" % p99
        # the shed is attributed to the flooding tenant in the counters
        assert fleet.stats()["counters"]["quota_shed"] == stats["shed"]
    finally:
        fleet.close()


def test_router_priority_maps_to_in_replica_eviction(tmp_path):
    """Priority classes resolved at the router ride into the replica's
    AdmissionQueue, so under replica overload the eviction order is
    exactly the PR-4 semantics: the lowest-priority, oldest request
    pays; a high-priority arrival is admitted."""
    fleet = _mk_fleet(
        1, tmp_path, latency=0.08,
        quotas={"bulk": TenantPolicy(priority=0),
                "vip": TenantPolicy(priority=7)},
        # tiny queue + slow exec: the single replica saturates instantly
        replica_env={0: {"MXNET_TPU_SERVE_QUEUE_DEPTH": "2",
                         "MXNET_TPU_SERVE_MAX_BATCH": "1",
                         "MXNET_TPU_SERVE_LINGER": "0"}},
        retry_max=1)      # no second replica: sheds must surface typed
    try:
        x = _row()
        bulk = [fleet.submit(data=x, tenant="bulk", deadline=3.0)
                for _ in range(8)]
        time.sleep(0.05)
        vip = fleet.submit(data=x, tenant="vip", deadline=3.0)
        outcomes = {"ok": 0, "Overloaded": 0}
        for req in bulk:
            try:
                req.result(timeout=6.0)
                outcomes["ok"] += 1
            except Overloaded:
                outcomes["Overloaded"] += 1
        vip.result(timeout=6.0)            # never shed, never evicted
        assert outcomes["Overloaded"] >= 1, outcomes
        assert outcomes["ok"] >= 1, outcomes
    finally:
        fleet.close()


def test_rolling_swap_under_load_with_rollback(tmp_path):
    """Rolling fleet swap under live load: zero failed requests during a
    good swap; a failing canary (chaos ``bad_swap`` on replica 1)
    triggers fleet-wide rollback with the OLD model still serving; a
    clean retry then lands the new model everywhere."""
    fleet = _mk_fleet(
        2, tmp_path, latency=0.002,
        replica_env={1: {"MXNET_TPU_CHAOS": "bad_swap"}})
    try:
        x = _row()
        res = {"ok": 0, "err": {}}
        stop_at = time.monotonic() + 4.0

        def loader():
            while time.monotonic() < stop_at:
                try:
                    fleet.predict(data=x, deadline=1.0)
                    res["ok"] += 1
                except Exception as e:
                    k = type(e).__name__
                    res["err"][k] = res["err"].get(k, 0) + 1

        threads = [threading.Thread(target=loader, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)

        spec = {"batch": 4, "features": 3, "latency": 0.002, "scale": 2.0}
        # first attempt: replica 0 swaps, replica 1's canary is poisoned
        # -> fleet-wide rollback, old model (scale 1) keeps serving
        with pytest.raises(SwapFailed):
            fleet.swap(spec, tag="v2")
        out = fleet.predict(data=x, deadline=1.0)
        assert float(out[0][0][0]) == pytest.approx(1.0)
        events = [e["event"] for e in _events(fleet)]
        assert "swap_fail" in events and "rollback" in events

        # retry (the one-shot chaos fault is consumed): lands everywhere
        assert fleet.swap(spec, tag="v2") == [0, 1]
        out = fleet.predict(data=x, deadline=1.0)
        assert float(out[0][0][0]) == pytest.approx(2.0)

        for t in threads:
            t.join(timeout=10.0)
        assert not res["err"], \
            "requests failed during rolling swaps: %s" % res
        assert res["ok"] > 100
        all_events = _events(fleet)
        events = [e["event"] for e in all_events]
        assert "swap_complete" in events
        assert events.count("drain") >= 3
        # warm-load on every replica: each swapped replica prewarmed the
        # incoming model BEFORE its drain (prewarm_ok precedes drain in
        # the event log) and activated the prewarmed standby (warm=True
        # echoed by the replica) — the drained window held nothing but
        # the pointer flip
        swap_oks = [e for e in all_events if e["event"] == "swap_ok"]
        assert swap_oks and all(e.get("warm") for e in swap_oks), swap_oks
        prewarm_rids = {e["replica"] for e in all_events
                        if e["event"] == "prewarm_ok"}
        assert {e["replica"] for e in swap_oks} <= prewarm_rids
        for rid in sorted(prewarm_rids):
            seq = [e["event"] for e in all_events
                   if e.get("replica") == rid
                   and e["event"] in ("prewarm_ok", "drain")]
            assert seq.index("prewarm_ok") < seq.index("drain"), seq
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# tools
# ---------------------------------------------------------------------------

def _run_servebench(extra, timeout=120):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "servebench.py"),
         "--json"] + extra,
        capture_output=True, text=True, timeout=timeout, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout[out.stdout.index("{"):])


def test_servebench_fleet_smoke():
    rep = _run_servebench(["--replicas", "2", "--duration", "1.5",
                           "--exec-latency", "0.004",
                           "--concurrency", "4", "--deadline", "0.5",
                           "--tenants", "search,ads"])
    assert rep["replicas"] == 2
    assert rep["ok"] > 20 and rep["late_ok"] == 0
    assert rep["ready_at_end"] == 2
    share = rep["per_replica_share"]
    assert set(share) == {"0", "1"}
    assert abs(share["0"] - share["1"]) < 0.5      # both replicas served
    assert "p99_ms" in rep["latency"]
    # per-tenant SLO block (additive schema): both synthetic tenants
    # show availability + budget burn, nobody shed
    tenants = rep["tenants"]
    assert set(tenants) == {"search", "ads"}
    for t in tenants.values():
        assert t["availability"] == 1.0
        assert t["budget_burn"]["p95"] < 1.0
        assert "latency_ms" in t


def test_postmortem_fleet_renders_timeline(tmp_path):
    path = tmp_path / "fleet-events.jsonl"
    events = [
        {"t": 1000.0, "event": "join", "replica": 0, "port": 4000},
        {"t": 1001.0, "event": "evict", "replica": 0, "cause": "link"},
        {"t": 1002.5, "event": "readmit", "replica": 0, "port": 4001},
        {"t": 1003.0, "event": "swap_begin", "targets": [0]},
        {"t": 1003.2, "event": "drain", "replica": 0},
        {"t": 1003.4, "event": "swap_ok", "replica": 0, "tag": "v2"},
        {"t": 1003.5, "event": "swap_complete", "replicas": [0]},
    ]
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "postmortem.py"),
         "--fleet", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "SERVING FLEET TIMELINE (7 event(s))" in out.stdout
    assert "evict" in out.stdout and "cause=link" in out.stdout
    assert "swap_ok" in out.stdout and "tag=v2" in out.stdout
    assert "evict=1" in out.stdout       # the summary line


# ---------------------------------------------------------------------------
# @slow: sustained drills + scaling
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_qps_scales_1_to_4_replicas(tmp_path):
    """Near-linear QPS 1 -> 4 replicas with bounded p99.  The synthetic
    executor sleeps (latency-bound), so replica processes genuinely
    parallelize even on one host core; the router/wire overhead is what
    could break linearity, and this guards it."""
    def measure(n, seconds=6.0):
        fleet = _mk_fleet(n, tmp_path / ("s%d" % n), latency=0.02)
        lat = []
        lock = threading.Lock()
        try:
            x = _row()
            stop_at = time.monotonic() + seconds
            done = [0]

            def worker():
                while time.monotonic() < stop_at:
                    t0 = time.monotonic()
                    fleet.predict(data=x, deadline=3.0)
                    with lock:
                        done[0] += 1
                        lat.append(time.monotonic() - t0)

            threads = [threading.Thread(target=worker, daemon=True)
                       for _ in range(16)]
            t_start = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=seconds + 30.0)
            elapsed = time.monotonic() - t_start
            lat.sort()
            return (done[0] / elapsed,
                    lat[max(0, int(len(lat) * 0.99) - 1)])
        finally:
            fleet.close()

    qps1, p99_1 = measure(1)
    qps4, p99_4 = measure(4)
    assert qps4 > 2.5 * qps1, \
        "QPS did not scale: 1 replica %.0f/s, 4 replicas %.0f/s" \
        % (qps1, qps4)
    # bounded p99: adding replicas must not grow the tail
    assert p99_4 < max(4 * p99_1, 0.5), \
        "p99 grew from %.3fs to %.3fs" % (p99_1, p99_4)


@pytest.mark.slow
def test_servebench_sustained_kill_drill():
    """The --kill-after acceptance drill at sustained load: a replica is
    SIGKILLed mid-run, the fleet sheds nothing, delivers zero late OKs,
    and ends with the relaunched replica re-enrolled."""
    rep = _run_servebench(["--replicas", "3", "--duration", "8",
                           "--exec-latency", "0.01",
                           "--concurrency", "8", "--deadline", "1.0",
                           "--kill-after", "3", "--kill-slot", "1"],
                          timeout=300)
    assert rep["kill"]["slot"] == 1
    assert rep["ok"] > 500
    assert rep["late_ok"] == 0
    assert not rep["errors"], rep["errors"]
    assert rep["evictions"] >= 1
    assert rep["redispatched"] + rep["hedge"]["fired"] >= 1
    assert rep["ready_at_end"] == 3
