#!/usr/bin/env python3
"""Load generator for the resilient serving runtime (mxnet_tpu/serving).

Drives a ServingRuntime — over a real AOT artifact or a synthetic
executor — in closed-loop (N workers, one in-flight request each) or
open-loop (fixed arrival rate, so overload and shedding are visible)
mode, and prints what a serving operator watches: latency percentiles,
shed rate by cause, queue depth, batch fill, and final health.

Usage:
    python tools/servebench.py [--artifact model.mxt] [options]

    --artifact PATH    serve a real exported artifact (default: a
                       synthetic executor — no device, no tracing — so
                       the runtime itself is what gets measured)
    --exec-latency S   synthetic executor time per batch (default 0.002)
    --batch N --features N   synthetic model shape (default 8 x 16)
    --mode closed|open       load shape (default closed)
    --concurrency N    closed-loop workers (default 8)
    --rate R           open-loop arrivals/sec (default 500)
    --duration S       wall-clock run time (default 2.0)
    --deadline S       per-request deadline (default 0.25)
    --priorities CSV   cycled per request, e.g. "0,0,0,2" (default "0")
    --queue-depth N / --max-batch N / --linger S   runtime knobs
    --json             emit ONE JSON document on stdout (for CI smoke)

Fleet mode (--replicas N) drives a replicated ServingFleet instead of a
single in-process runtime: N replica processes behind the router
(mxnet_tpu/serving/fleet.py), reporting fleet-level p50/p95/p99,
per-replica QPS share, shed-by-cause, hedge/eviction counters, and a
LATE-OK count (any OK result delivered past its deadline — the fleet's
acceptance invariant is that this is always zero):

    --replicas N       run N replica processes behind the fleet router
    --kill-after S     SIGKILL one replica S seconds into the run (the
                       kill-one-replica acceptance drill; the supervisor
                       relaunches it and the router re-admits it)
    --kill-slot K      which replica --kill-after kills (default 0)
    --tenant-rate R    per-tenant quota for the synthetic tenants
                       (default: unlimited)

The measurement loop is stdlib-only (threading/time/statistics); chaos
faults armed via MXNET_TPU_CHAOS (slow_exec/exec_error) apply to the
dispatch path as in production, making this the serving drill driver.
"""
import argparse
import json
import os
import statistics
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


class SyntheticProgram:
    """Program-like stand-in: fixed batch shape, configurable latency,
    identity-ish math — measures the runtime, not a device."""

    def __init__(self, batch, features, latency):
        import numpy as np
        self.input_names = ["data"]
        self.input_shapes = {"data": (batch, features)}
        self.input_dtypes = {"data": np.dtype(np.float32)}
        self.output_shapes = [(batch, features)]
        self.latency = latency
        self._np = np

    def forward(self, data):
        if self.latency:
            time.sleep(self.latency)
        return [self._np.tanh(data)]


def _percentiles(hist):
    """Latency block from a telemetry histogram — the SAME percentile
    implementation the serving runtime's stats() uses (single source of
    truth; the old private sorted-list math is gone)."""
    s = hist.summary()
    if not s["count"]:
        return {}
    ps = hist.percentiles((0.50, 0.95, 0.99))
    return {"p50_ms": round(ps[0.50] * 1e3, 3),
            "p95_ms": round(ps[0.95] * 1e3, 3),
            "p99_ms": round(ps[0.99] * 1e3, 3),
            "max_ms": round(s["max"] * 1e3, 3),
            "mean_ms": round(s["mean"] * 1e3, 3)}


class Collector:
    """Thread-safe outcome tally: ok latencies (into a telemetry
    histogram) + typed-error counts + late-OK detection (an OK result
    whose measured latency exceeds its deadline — the invariant both the
    runtime and the fleet router promise is that this NEVER happens)."""

    def __init__(self, deadline=None):
        from mxnet_tpu import telemetry
        self._lock = threading.Lock()
        # reservoir sized past any bench run so percentiles stay exact
        self.hist = telemetry.Histogram("servebench.latency_seconds",
                                        registered=False, always=True,
                                        reservoir=1 << 17)
        self.errors = {}
        self.total = 0
        self.late_ok = 0
        self._deadline = deadline

    @property
    def ok(self):
        return self.hist.summary()["count"]

    def record_ok(self, latency):
        with self._lock:
            self.total += 1
            # small slack: the worker measures wall time around
            # submit+result, which includes its own scheduling delay
            if (self._deadline is not None
                    and latency > self._deadline + 0.05):
                self.late_ok += 1
        self.hist.observe(latency)

    def record_error(self, exc):
        kind = type(exc).__name__
        with self._lock:
            self.total += 1
            self.errors[kind] = self.errors.get(kind, 0) + 1


def _example(prog):
    """One example row (batch-dim stripped) for every model input."""
    import numpy as np
    return {n: np.zeros(tuple(prog.input_shapes[n][1:]),
                        prog.input_dtypes[n]) for n in prog.input_names}


def run_closed(rt, prog, args, collector, stop_at, priorities,
               tenants=None):
    """Closed loop: each worker keeps exactly one request in flight."""
    example = _example(prog)
    counter = [0]
    lock = threading.Lock()

    def worker():
        while time.monotonic() < stop_at:
            with lock:
                counter[0] += 1
                n = counter[0]
                prio = priorities[n % len(priorities)]
            kw = {"priority": prio, "deadline": args.deadline}
            if tenants:
                kw["tenant"] = tenants[n % len(tenants)]
            t0 = time.monotonic()
            try:
                req = rt.submit(dict(example), **kw)
                req.result(timeout=args.deadline + 5.0)
                collector.record_ok(time.monotonic() - t0)
            except Exception as e:
                collector.record_error(e)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(args.concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=args.duration + 30.0)


def run_open(rt, prog, args, collector, stop_at, priorities, tenants=None):
    """Open loop: arrivals at a fixed rate regardless of completions —
    the load shape that actually exposes shedding behavior."""
    example = _example(prog)
    interval = 1.0 / args.rate
    pending = []
    n = 0
    next_at = time.monotonic()
    while time.monotonic() < stop_at:
        now = time.monotonic()
        if now < next_at:
            time.sleep(min(interval, next_at - now))
            continue
        next_at += interval
        n += 1
        kw = {"priority": priorities[n % len(priorities)],
              "deadline": args.deadline}
        if tenants:
            kw["tenant"] = tenants[n % len(tenants)]
        t0 = time.monotonic()
        try:
            req = rt.submit(dict(example), **kw)
            pending.append((t0, req))
        except Exception as e:
            collector.record_error(e)
    for t0, req in pending:
        try:
            req.result(timeout=args.deadline + 5.0)
            collector.record_ok(req.latency if req.latency is not None
                                else time.monotonic() - t0)
        except Exception as e:
            collector.record_error(e)


def _main_decode(args):
    """--decode: drive the interactive decode engine (mxnet_tpu/serving/
    decode) with an open-loop stream of MIXED-length generation requests
    and report what an interactive-serving operator watches: tokens/sec/
    chip, per-token p50/p99, batch occupancy — plus the continuous-vs-
    static batching comparison on the SAME job list and step program
    (static = classic close-the-batch-and-run-to-the-longest; the
    wasted idle slots are exactly what token-level admission wins back).
    """
    import numpy as np
    import jax
    from mxnet_tpu.serving.decode import (DecodeConfig, DecodeEngine,
                                          DecodeProgram,
                                          init_decode_params)

    cfg = DecodeConfig(args.decode_vocab, args.decode_layers,
                       args.decode_hidden, args.decode_heads,
                       args.decode_seq, page_size=args.decode_page,
                       max_seqs=args.decode_slots,
                       quantize=args.decode_quant or None)
    prog = DecodeProgram(init_decode_params(cfg, seed=0), cfg,
                         name="servebench-decode")
    prog.ensure_compiled()
    # an un-meshed program serves from the default device alone; the
    # report names it, and the rates below are that one device's
    from mxnet_tpu.context import device_summary
    dev = jax.devices()[0]
    rs = np.random.RandomState(0)
    plens = [int(x) for x in args.decode_prompts.split(",")]
    nnews = [int(x) for x in args.decode_new.split(",")]
    jobs = [(rs.randint(0, cfg.vocab_size, plens[i % len(plens)])
             .astype(np.int32), nnews[i % len(nnews)])
            for i in range(args.requests)]

    # -- static batching baseline: batches of S close, run to the
    # longest member, next batch starts only when the previous finishes
    S = cfg.max_seqs
    pp = cfg.pages_per_seq
    table = np.zeros((S, pp), np.int32)
    for s in range(S):
        table[s] = 1 + s * pp + np.arange(pp)
    kv = prog.fresh_cache()
    static_tokens = 0
    static_steps = 0
    static_lat = []
    t_static0 = time.monotonic()
    for g0 in range(0, len(jobs), S):
        group = jobs[g0:g0 + S]
        total = [len(p) + n for p, n in group]
        steps = max(total) - 1            # last token needs no write+step
        gen = [[] for _ in group]
        for t in range(steps + 1):
            toks = np.zeros(S, np.int32)
            for i, (p, _n) in enumerate(group):
                toks[i] = p[t] if t < len(p) else (
                    gen[i][-1] if gen[i] else 0)
            pos = np.full(S, t, np.int32)
            nxt, _lg, kv = prog.step(
                kv, toks, pos, pos + 1,
                table[np.arange(S), t // cfg.page_size],
                np.full(S, t % cfg.page_size, np.int32), table)
            nxt = np.asarray(nxt)
            static_steps += 1
            for i, (p, n) in enumerate(group):
                if t >= len(p) - 1 and len(gen[i]) < n:
                    gen[i].append(int(nxt[i]))
        now = time.monotonic()
        for i, (p, n) in enumerate(group):
            static_tokens += len(gen[i])
            static_lat.append(now - t_static0)    # group completion
        kv = prog.fresh_cache()                   # next batch, fresh pool
    static_wall = time.monotonic() - t_static0
    static_occ = static_tokens / max(static_steps * S, 1)

    # -- continuous batching: the same jobs through the engine
    from mxnet_tpu import telemetry
    eng = DecodeEngine(prog, default_deadline=args.deadline
                       if args.deadline > 0 else None,
                       queue_depth=max(64, len(jobs)))
    lat_hist = telemetry.Histogram("servebench.decode_latency",
                                   registered=False, always=True)
    t_cont0 = time.monotonic()
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in jobs]
    cont_tokens = 0
    errors = {}
    for r in reqs:
        try:
            out = r.result(timeout=120.0)
            cont_tokens += int(out[0].size)
            lat_hist.observe(r.latency)
        except Exception as e:
            errors[type(e).__name__] = errors.get(type(e).__name__, 0) + 1
    cont_wall = time.monotonic() - t_cont0
    stats = eng.stats()
    eng.close()

    d = stats["decode"]
    report = {
        "mode": "decode",
        "requests": len(jobs),
        "slots": S,
        "device": device_summary([dev]),
        "geometry": "L%d H%d heads%d V%d T%d page%d%s" % (
            cfg.num_layers, cfg.hidden, cfg.heads, cfg.vocab_size,
            cfg.max_seq_len, cfg.page_size,
            " %s" % cfg.quantize if cfg.quantize else ""),
        "continuous": {
            "wall_s": round(cont_wall, 3),
            "tokens": cont_tokens,
            "tokens_per_sec": round(cont_tokens / cont_wall, 1),
            "occupancy_mean": d["occupancy_mean"],
            "latency": _percentiles(lat_hist),
            "errors": errors,
        },
        "static": {
            "wall_s": round(static_wall, 3),
            "tokens": static_tokens,
            "tokens_per_sec": round(static_tokens / static_wall, 1),
            "occupancy_mean": round(static_occ, 4),
            "latency": {"p50_ms": round(
                1e3 * statistics.median(static_lat), 3),
                "p99_ms": round(1e3 * sorted(static_lat)[
                    max(0, int(0.99 * len(static_lat)) - 1)], 3)},
        },
        "per_token_step": d.get("token_step_s", {}),
        "compiles": d["compiles"],
        "decode_stats": d,
    }
    report["continuous_vs_static"] = round(
        report["continuous"]["tokens_per_sec"] /
        max(report["static"]["tokens_per_sec"], 1e-9), 3)
    # prediction-conformance mirror: measured decode tokens/s vs the
    # analytic decode budget (analysis/predict.py), plus the input-bound
    # verdict when an input pipeline fed this process — same sections
    # the attribution reports carry
    try:
        from mxnet_tpu.analysis import predict as _predict
        from mxnet_tpu.telemetry import perf as _perf
        budget = _predict.predict_decode_budget(
            cfg.num_layers, cfg.hidden, cfg.vocab_size, S,
            cfg.max_seq_len, name="servebench.decode",
            quant_bits={"int8": 8, "int4": 4}.get(cfg.quantize, 32))
        conf = _predict.conformance(budget, {
            "decode_tokens_per_s":
                report["continuous"]["tokens_per_sec"]})
        if conf:
            report["conformance"] = conf
        iv = _perf.input_verdict(
            step_s=cont_wall / max(cont_tokens, 1))
        if iv:
            report["input_bound"] = iv
    except Exception:
        pass
    if args.json:
        json.dump(report, sys.stdout, indent=2, sort_keys=True)
        print()
        return 0
    print("servebench --decode: %d mixed-length requests over %d slots "
          "(%s) on one %s %s" % (len(jobs), S, report["geometry"],
                                 dev.platform, dev.device_kind))
    print("  %-12s %10s %14s %10s %10s %10s" %
          ("batching", "wall s", "tokens/s", "occupancy",
           "p50 ms", "p99 ms"))
    for name in ("continuous", "static"):
        r = report[name]
        lat = r["latency"]
        print("  %-12s %10.3f %14.1f %10.3f %10s %10s"
              % (name, r["wall_s"], r["tokens_per_sec"],
                 r["occupancy_mean"], lat.get("p50_ms", "-"),
                 lat.get("p99_ms", "-")))
    print("  continuous / static throughput: %.2fx  (compiles: %d)"
          % (report["continuous_vs_static"], report["compiles"]))
    if errors:
        print("  errors          %s" % errors)
    return 0


def _main_fleet(args):
    """--replicas N: drive a replicated ServingFleet and report the
    fleet-level view (percentiles, per-replica share, shed-by-cause,
    hedge/eviction counters, late-OK invariant)."""
    from mxnet_tpu.serving.fleet import ServingFleet

    tenants = [t for t in args.tenants.split(",") if t]
    quotas = ({t: {"rate": args.tenant_rate} for t in tenants}
              if args.tenant_rate and tenants else None)
    fleet = ServingFleet(
        args.replicas,
        artifact=args.artifact,
        synthetic=(None if args.artifact else
                   (args.batch, args.features, args.exec_latency)),
        quotas=quotas)
    prog = SyntheticProgram(args.batch, args.features, 0)
    if args.artifact:
        # mirror the fleet's real schema for input synthesis
        schema = fleet.router._schema
        prog.input_names = schema["input_names"]
        prog.input_shapes = {n: tuple(schema["input_shapes"][n])
                             for n in prog.input_names}
        import numpy as np
        prog.input_dtypes = {n: np.dtype(schema["input_dtypes"][n])
                             for n in prog.input_names}
    priorities = [int(p) for p in args.priorities.split(",")]
    collector = Collector(deadline=args.deadline)
    kill = {}
    stop_at = time.monotonic() + args.duration

    def killer():
        time.sleep(args.kill_after)
        kill["pid"] = fleet.kill_replica(args.kill_slot)
        kill["slot"] = args.kill_slot
        kill["at_s"] = round(args.kill_after, 3)
        print("servebench: SIGKILLed replica %d (pid %s) at t+%.1fs"
              % (args.kill_slot, kill["pid"], args.kill_after),
              file=sys.stderr)

    if args.kill_after is not None:
        threading.Thread(target=killer, daemon=True).start()
    t_start = time.monotonic()
    try:
        if args.mode == "closed":
            run_closed(fleet.router, prog, args, collector, stop_at,
                       priorities, tenants=tenants)
        else:
            run_open(fleet.router, prog, args, collector, stop_at,
                     priorities, tenants=tenants)
        # let an in-drill relaunch finish re-enrolling before snapshotting
        if args.kill_after is not None:
            fleet.router.wait_ready(args.replicas, timeout=15.0)
    finally:
        stats = fleet.stats()
        fleet.close()
    elapsed = time.monotonic() - t_start

    n_ok = collector.ok
    dispatches = {str(rid): r.get("dispatches", 0)
                  for rid, r in stats["replicas"].items()}
    total_disp = max(sum(dispatches.values()), 1)
    c = stats["counters"]
    shed_by_cause = {k[4:]: v for k, v in c.items()
                     if k.startswith("err:")}
    shed_by_cause.update({k: v for k, v in collector.errors.items()})
    report = {
        "mode": args.mode,
        "replicas": args.replicas,
        "duration_s": round(elapsed, 3),
        "requests": collector.total,
        "ok": n_ok,
        "late_ok": collector.late_ok,
        "throughput_rps": round(n_ok / max(elapsed, 1e-9), 1),
        "errors": collector.errors,
        "shed_by_cause": shed_by_cause,
        "latency": _percentiles(collector.hist),
        "per_replica_share": {rid: round(n / total_disp, 4)
                              for rid, n in sorted(dispatches.items())},
        "hedge": {"fired": c.get("hedge_fired", 0),
                  "won": c.get("hedge_won", 0)},
        "evictions": c.get("evictions", 0),
        "redispatched": c.get("redispatched", 0),
        "quota_shed": c.get("quota_shed", 0),
        "ready_at_end": sum(1 for r in stats["replicas"].values()
                            if r["state"] == "READY"),
        # per-tenant SLO table (router TenantSLO ledgers): availability,
        # latency percentiles, deadline-budget burn, shed-by-cause —
        # additive schema
        "tenants": stats.get("tenants", {}),
        "fleet_stats": stats,
    }
    if kill:
        report["kill"] = kill
    if args.json:
        json.dump(report, sys.stdout, indent=2, sort_keys=True,
                  default=repr)
        print()
        return 0
    print("servebench: fleet of %d, %s loop, %.2fs"
          % (args.replicas, args.mode, elapsed))
    print("  requests        %d (ok %d, %.1f ok/s)  LATE OKs %d"
          % (collector.total, n_ok, report["throughput_rps"],
             collector.late_ok))
    if report["latency"]:
        print("  latency ms      p50 %(p50_ms)s  p95 %(p95_ms)s  "
              "p99 %(p99_ms)s  max %(max_ms)s" % report["latency"])
    print("  shed by cause   %s" % (report["shed_by_cause"] or "none"))
    print("  replica share   %s" % report["per_replica_share"])
    print("  hedges          fired %d, won %d; evictions %d, "
          "redispatched %d, quota shed %d"
          % (report["hedge"]["fired"], report["hedge"]["won"],
             report["evictions"], report["redispatched"],
             report["quota_shed"]))
    if kill:
        print("  kill drill      replica %(slot)s pid %(pid)s at "
              "t+%(at_s)ss" % kill)
    for name, t in sorted((report["tenants"] or {}).items()):
        lat = t.get("latency_ms") or {}
        burn = t.get("budget_burn") or {}
        avail = t.get("availability")
        print("  tenant %-9s req %-6d ok %-6d avail %-7s p95 %-8s "
              "burn_p95 %-7s shed %s"
              % (name, t.get("requests", 0), t.get("ok", 0),
                 "-" if avail is None else "%.1f%%" % (100 * avail),
                 lat.get("p95", "-"), burn.get("p95", "-"),
                 t.get("shed") or 0))
    print("  ready at end    %d/%d" % (report["ready_at_end"],
                                       args.replicas))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--artifact")
    ap.add_argument("--exec-latency", type=float, default=0.002)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--mode", choices=("closed", "open"), default="closed")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--rate", type=float, default=500.0)
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--deadline", type=float, default=0.25)
    ap.add_argument("--priorities", default="0")
    ap.add_argument("--queue-depth", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--linger", type=float, default=0.002)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--replicas", type=int, default=0,
                    help="fleet mode: N replica processes behind the "
                         "router (0 = single in-process runtime)")
    ap.add_argument("--kill-after", type=float, default=None,
                    help="fleet mode: SIGKILL one replica this many "
                         "seconds into the run (supervisor relaunches)")
    ap.add_argument("--kill-slot", type=int, default=0)
    ap.add_argument("--tenants", default="",
                    help="fleet mode: tenant names cycled per request")
    ap.add_argument("--tenant-rate", type=float, default=None,
                    help="fleet mode: per-tenant token-bucket rate")
    ap.add_argument("--decode", action="store_true",
                    help="decode mode: mixed-length generation streams "
                         "through the continuous-batching engine, with "
                         "a continuous-vs-static comparison table")
    ap.add_argument("--requests", type=int, default=24,
                    help="decode mode: number of generation requests")
    ap.add_argument("--decode-prompts", default="4,12,24",
                    help="decode mode: prompt lengths, cycled")
    ap.add_argument("--decode-new", default="4,16,8",
                    help="decode mode: max new tokens, cycled")
    ap.add_argument("--decode-layers", type=int, default=2)
    ap.add_argument("--decode-hidden", type=int, default=64)
    ap.add_argument("--decode-heads", type=int, default=4)
    ap.add_argument("--decode-vocab", type=int, default=256)
    ap.add_argument("--decode-seq", type=int, default=64)
    ap.add_argument("--decode-page", type=int, default=8)
    ap.add_argument("--decode-slots", type=int, default=4)
    ap.add_argument("--decode-quant", default="",
                    help="decode mode: int8/int4 weight-only quantized "
                         "matmuls")
    args = ap.parse_args(argv)
    if args.decode:
        return _main_decode(args)
    if args.replicas:
        return _main_fleet(args)
    if args.kill_after is not None or args.tenants or args.tenant_rate:
        ap.error("--kill-after/--tenants/--tenant-rate need --replicas N")

    from mxnet_tpu.serving import ServingRuntime

    if args.artifact:
        prog = args.artifact
    else:
        prog = SyntheticProgram(args.batch, args.features, args.exec_latency)
    priorities = [int(p) for p in args.priorities.split(",")]
    rt = ServingRuntime(prog, queue_depth=args.queue_depth,
                        max_batch_rows=args.max_batch, linger=args.linger,
                        default_deadline=args.deadline, name="servebench")
    prog = rt._program        # resolve artifact path -> loaded program

    collector = Collector(deadline=args.deadline)
    depth_samples = []
    stop_at = time.monotonic() + args.duration
    sampling = [True]

    def sampler():
        while sampling[0]:
            depth_samples.append(len(rt._queue))
            time.sleep(0.01)

    s = threading.Thread(target=sampler, daemon=True)
    s.start()
    t_start = time.monotonic()
    try:
        if args.mode == "closed":
            run_closed(rt, prog, args, collector, stop_at, priorities)
        else:
            run_open(rt, prog, args, collector, stop_at, priorities)
    finally:
        sampling[0] = False
        s.join(timeout=1.0)
        stats = rt.stats()
        rt.close()
    elapsed = time.monotonic() - t_start

    shed = sum(v for k, v in collector.errors.items()
               if k in ("Overloaded", "CircuitOpen"))
    n_ok = collector.ok
    report = {
        "mode": args.mode,
        "duration_s": round(elapsed, 3),
        "requests": collector.total,
        "ok": n_ok,
        "late_ok": collector.late_ok,
        "throughput_rps": round(n_ok / max(elapsed, 1e-9), 1),
        "errors": collector.errors,
        "shed_rate": round(shed / max(collector.total, 1), 4),
        "latency": _percentiles(collector.hist),
        "queue_depth_max": max(depth_samples) if depth_samples else 0,
        "queue_depth_mean": round(statistics.fmean(depth_samples), 2)
        if depth_samples else 0.0,
        # exec-span device time / wall, from the attribution plane's
        # serving exec histogram (surfaced top-level: the one number an
        # operator sizes a fleet by)
        "device_utilization": stats.get("device_utilization"),
        "runtime_stats": stats,
    }
    # input-bound mirror (attribution report schema): present only when
    # a data pipeline's fetch span was measured in this process
    try:
        from mxnet_tpu.telemetry import perf as _perf
        iv = _perf.input_verdict()
        if iv:
            report["input_bound"] = iv
    except Exception:
        pass
    if args.json:
        json.dump(report, sys.stdout, indent=2, sort_keys=True)
        print()
        return 0
    print("servebench: %(mode)s loop, %(duration_s).2fs" % report)
    print("  requests        %(requests)d (ok %(ok)d, %(throughput_rps).1f"
          " ok/s)" % report)
    print("  shed rate       %.1f%%  errors %s"
          % (100 * report["shed_rate"], report["errors"] or "none"))
    if report["latency"]:
        print("  latency ms      p50 %(p50_ms)s  p95 %(p95_ms)s  "
              "p99 %(p99_ms)s  max %(max_ms)s" % report["latency"])
    print("  queue depth     max %d  mean %.2f  (bound %d)"
          % (report["queue_depth_max"], report["queue_depth_mean"],
             args.queue_depth))
    print("  batches         %d (%.2f rows avg)  health %s"
          % (stats["counters"].get("batches", 0),
             stats["counters"].get("rows", 0) /
             max(stats["counters"].get("batches", 1), 1),
             stats["health"]))
    if report["device_utilization"] is not None:
        print("  device util     %.1f%% (exec-span time / wall)"
              % (100 * report["device_utilization"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
