"""Performance attribution plane: automatic roofline/MFU accounting.

Every perf round so far (PERF.md r2-r5) re-derived the same numbers by
hand: HLO FLOPs, HBM bytes by op class, copy counts, collective
payloads, roofline shares.  This module makes that accounting an
always-available instrument: point it at any compiled program and it
emits one **attribution report** combining

* the static analytics from :mod:`mxnet_tpu.analysis.costmodel`
  (analytic FLOPs, instruction bytes by op class × dtype with the
  f32-vs-bf16 split, collective payloads + wire model, static
  collective/compute overlap),
* XLA's own ``Compiled.cost_analysis()`` (flops / bytes-accessed — the
  5%-agreement cross-check is CI-enforced), and
* the measured side from the telemetry layer: the ``train.step_seconds``
  histogram and the host-enqueue vs device-block span split recorded by
  ``ShardedTrainer.step``

into roofline position (compute- vs HBM- vs collective- vs host-bound),
MFU vs chip peak, top-N byte/FLOP contributors, and the
measured-vs-analytic step-time ratio.  Rendered as JSON (atomic write,
``analysis/report.py`` discipline), pretty text, and a Perfetto counter
track that drops into the merged trace.

Wire-up (``MXNET_TPU_ATTRIBUTION=1``): every compiled entry point —
``ShardedTrainer`` step (lazy jit and ``build_step_auto_layout``),
``Module.bind``, the ring/pipeline/moe collectives, ``ServedProgram``
— writes one report per distinct program into the watchdog/preflight
report dir (``attribution-<name>-*.json``).  Each is attributed ONCE
per (name, input signature); the hooks never raise into the entry
point.  A caller that holds a compiled step can call
:func:`attribute_compiled` directly; :func:`phases_block` is the
report's compact form.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Dict, Optional

__all__ = ["AttributionReport", "attribute_after_steps",
           "attribute_compiled", "attribute_fn", "attribute_module",
           "enabled", "input_verdict", "maybe_attribute",
           "maybe_attribute_fn", "maybe_attribute_module",
           "phases_block", "report_dir", "reset_attributed"]

_SEQ = [0]
_DONE_LOCK = threading.Lock()
_DONE = set()          # (name, signature) pairs already attributed


def enabled() -> bool:
    return os.environ.get("MXNET_TPU_ATTRIBUTION", "0") not in (
        "0", "", "false", "off")


def attribute_after_steps() -> int:
    """How many steps the trainer hook waits before attributing (so the
    step histograms hold real samples); MXNET_TPU_ATTRIBUTION_AFTER."""
    try:
        return max(1, int(os.environ.get("MXNET_TPU_ATTRIBUTION_AFTER",
                                         "3")))
    except ValueError:
        return 3


def report_dir() -> str:
    """Same forensics directory as preflight reports and watchdog
    post-mortems: one place to look."""
    explicit = os.environ.get("MXNET_TPU_ATTRIBUTION_DIR")
    if explicit:
        return explicit
    from ..analysis import preflight as _preflight
    return _preflight.report_dir()


class AttributionReport:
    """One program's attribution: analytics + measurement, renderable as
    JSON / pretty text / a Perfetto counter track."""

    def __init__(self, data: Dict):
        self.data = data

    # -- accessors used by gates/tests ---------------------------------
    @property
    def program(self) -> str:
        return self.data.get("program", "?")

    @property
    def mfu(self) -> Optional[float]:
        return self.data.get("step", {}).get("mfu")

    def to_dict(self) -> Dict:
        return self.data

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.data, indent=indent, default=repr)

    @classmethod
    def load(cls, path: str) -> "AttributionReport":
        with open(path) as f:
            return cls(json.load(f))

    def save(self, path: str) -> str:
        """Atomic JSON write (temp+replace, analysis/report.py model)."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_json())
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path

    def perfetto_counters(self, ts_us: Optional[float] = None) -> list:
        """Chrome-trace counter events (``ph: "C"``) for the headline
        numbers — merged into the profiler trace when it runs, so the
        roofline shares sit as counter tracks above the span timeline."""
        ts = time.perf_counter() * 1e6 if ts_us is None else ts_us
        shares = self.data.get("roofline", {}).get("shares", {})
        events = []
        base = "attribution/%s" % self.program
        if shares:
            events.append({"name": base + "/roofline_share", "ph": "C",
                           "ts": ts, "pid": 2, "tid": 0,
                           "args": {k: shares[k] for k in sorted(shares)}})
        step = self.data.get("step", {})
        vals = {k: step[k] for k in ("mfu", "measured_s")
                if step.get(k) is not None}
        if vals:
            events.append({"name": base + "/step", "ph": "C", "ts": ts,
                           "pid": 2, "tid": 0, "args": vals})
        ov = self.data.get("overlap", {})
        if ov.get("overlap_pct") is not None:
            events.append({"name": base + "/overlap_pct", "ph": "C",
                           "ts": ts, "pid": 2, "tid": 0,
                           "args": {"pct": ov["overlap_pct"]}})
        mem = self.data.get("memory", {})
        peak = (mem.get("compiled") or {}).get("peak_bytes") \
            or (mem.get("predicted") or {}).get("peak_bytes")
        if peak:
            events.append({"name": base + "/memory_bytes", "ph": "C",
                           "ts": ts, "pid": 2, "tid": 0,
                           "args": {"peak": peak}})
        conf = self.data.get("conformance")
        if conf:
            events.append({"name": base + "/conformance", "ph": "C",
                           "ts": ts, "pid": 2, "tid": 0,
                           "args": {m: info["ratio"] for m, info
                                    in conf["metrics"].items()}})
        return events

    def pretty(self) -> str:
        d = self.data
        rule = "=" * 72
        lines = [rule, "ATTRIBUTION %s" % d.get("program", "?"), rule]
        topo = d.get("topology", {})
        lines.append("topology: %s %s x%d" % (
            topo.get("platform", "?"), topo.get("device_kind", "?"),
            topo.get("n_devices", 1)))
        a = d.get("analytic", {})
        hc = d.get("hlo_cost", {})
        lines.append(
            "flops/device-step: analytic %.3e | XLA cost analysis %s "
            "(ratio %s)" % (
                a.get("flops", 0.0),
                ("%.3e" % hc["flops"]) if hc.get("flops") else "n/a",
                hc.get("flops_ratio_analytic_vs_hlo", "n/a")))
        lines.append("bytes: instruction %.3e | HBM accessed %s" % (
            a.get("instruction_bytes_total", 0.0),
            ("%.3e" % hc["bytes_accessed"]) if hc.get("bytes_accessed")
            else "n/a"))
        split = a.get("bytes_by_dtype", {})
        if split:
            lines.append("dtype split: " + ", ".join(
                "%s %.2f GB" % (dt, b / 1e9) for dt, b in split.items()))
        for i, c in enumerate(a.get("top_contributors", [])[:5]):
            lines.append("  top%d  %-24s %-5s %10.3f MB"
                         % (i + 1, c["op"], c["dtype"], c["bytes"] / 1e6))
        coll = a.get("collectives") or {}
        for kind in sorted(coll):
            info = coll[kind]
            lines.append("collective %-20s %3d ops  %.2f MB payload"
                         % (kind, info["count"], info["bytes"] / 1e6))
        by_axis = a.get("collectives_by_axis") or {}
        if by_axis:
            lines.append("collective bytes by axis: " + ", ".join(
                "%s %.2f MB" % (ax, b / 1e6)
                for ax, b in sorted(by_axis.items())))
        ov = d.get("overlap", {})
        if ov.get("overlap_pct") is not None:
            lines.append("collective/compute overlap: %.1f%% of %.2f MB "
                         "(%d async / %d sync ops, %d pipelined)"
                         % (ov["overlap_pct"],
                            ov["collective_bytes"] / 1e6,
                            ov["async_ops"], ov["sync_ops"],
                            ov.get("pipelined_ops", 0)))
        r = d.get("roofline", {})
        if r:
            lines.append(
                "roofline: compute %.3es | hbm %.3es | collective %.3es "
                "-> %s-bound" % (r.get("compute_s", 0.0),
                                 r.get("hbm_s", 0.0),
                                 r.get("collective_s", 0.0),
                                 r.get("bound", "?")))
            if r.get("shares"):
                lines.append("shares of step: " + ", ".join(
                    "%s %.0f%%" % (k, 100 * v)
                    for k, v in sorted(r["shares"].items())))
        mem = d.get("memory", {})
        mc = mem.get("compiled") or {}
        mp = mem.get("predicted") or {}
        if mc or mp.get("peak_bytes"):
            lines.append(
                "memory: predicted io %.2f MB vs compiled io %s "
                "(ratio %s); compiled peak %s (temp %s, aliased %s)" % (
                    (mp.get("argument_bytes", 0)
                     + mp.get("output_bytes", 0)) / 1e6,
                    "%.2f MB" % ((mc.get("argument_bytes", 0)
                                  + mc.get("output_bytes", 0)) / 1e6)
                    if mc else "n/a",
                    mem.get("predicted_vs_compiled", "n/a"),
                    "%.2f MB" % (mc["peak_bytes"] / 1e6)
                    if mc.get("peak_bytes") is not None else "n/a",
                    "%.2f MB" % (mc.get("temp_bytes", 0) / 1e6)
                    if mc else "n/a",
                    "%.2f MB" % (mc.get("alias_bytes", 0) / 1e6)
                    if mc else "n/a"))
        mm = mem.get("measured") or {}
        if mm.get("live_bytes"):
            lines.append("measured live %.2f MB (peak %.2f MB)" % (
                mm["live_bytes"] / 1e6,
                mm.get("peak_live_bytes", 0) / 1e6))
        s = d.get("step", {})
        if s.get("measured_s"):
            lines.append(
                "step: measured %.4fs (host-enqueue %s, device-wait %s); "
                "measured/analytic %s" % (
                    s["measured_s"],
                    "%.4fs" % s["host_enqueue_s"]
                    if s.get("host_enqueue_s") is not None else "n/a",
                    "%.4fs" % s["device_wait_s"]
                    if s.get("device_wait_s") is not None else "n/a",
                    r.get("measured_vs_analytic", "n/a")))
        if s.get("mfu") is not None:
            lines.append("MFU vs chip peak: %.4f" % s["mfu"])
        if r.get("input_share") is not None:
            lines.append(
                "input pipeline: fetch p50 %s, share %.0f%% of "
                "(fetch+step)%s" % (
                    "%.4fs" % s["io_s"] if s.get("io_s") is not None
                    else "n/a", 100 * r["input_share"],
                    "  -> INPUT-BOUND" if r.get("bound") == "input"
                    else ""))
        conf = d.get("conformance")
        if conf:
            lines.append("conformance vs budget [%s]: %s" % (
                conf.get("verdict", "?"),
                ", ".join("%s x%.2f %s"
                          % (m, info["ratio"], info["verdict"])
                          for m, info in sorted(
                              conf.get("metrics", {}).items()))))
        lines.append("")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# core: attribute a compiled program
# ---------------------------------------------------------------------------

def _cost_analysis(compiled) -> Dict:
    """Normalized ``Compiled.cost_analysis()``: {} when the executable
    cannot report (e.g. a deserialized AOT artifact on some backends)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def _measured_from_telemetry():
    """(step_s, host_s, device_s) medians from the registry histograms
    ShardedTrainer.step feeds — None where nothing was observed."""
    from . import registry as _registry

    def p50(name):
        try:
            h = _registry.histogram(name)
        except TypeError:
            return None
        ps = h.percentiles((0.5,))
        return ps.get(0.5)

    return (p50("train.step_seconds"), p50("train.host_enqueue_seconds"),
            p50("train.device_wait_seconds"))


def input_verdict(step_s: Optional[float] = None,
                  io_s: Optional[float] = None,
                  min_samples: int = 2) -> Optional[Dict]:
    """ROADMAP item 4's rule: the run is **input-bound** when the data
    pipeline's synchronous fetch (the ``data.next_seconds`` span every
    iterator records) rivals the step itself — no device roofline
    position matters if the accelerator is waiting on the host loader.

    Returns ``{"io_s", "step_s", "input_share", "bound_input"}`` with
    ``input_share = io / (io + step)`` (both p50), ``bound_input`` when
    the share crosses 0.5; None when either histogram is missing or the
    io histogram holds fewer than ``min_samples`` samples (a single
    cold fetch is warmup, not a verdict)."""
    from . import registry as _registry

    def h50(name):
        try:
            h = _registry.histogram(name)
        except TypeError:
            return None, 0
        s = h.summary()
        return s.get("p50"), s.get("count") or 0

    if io_s is None:
        io_s, n = h50("data.next_seconds")
        if io_s is None or n < min_samples:
            return None
    if step_s is None:
        step_s, _ = h50("train.step_seconds")
    if not step_s or not io_s:
        return None
    share = float(io_s) / (float(io_s) + float(step_s))
    return {"io_s": round(float(io_s), 6),
            "step_s": round(float(step_s), 6),
            "input_share": round(share, 4),
            "bound_input": share > 0.5}


def attribute_compiled(compiled, name: str, n_devices: int = 1,
                       ring_n: Optional[int] = None,
                       measured_step_s: Optional[float] = None,
                       host_s: Optional[float] = None,
                       device_s: Optional[float] = None,
                       hlo_text: Optional[str] = None,
                       mesh=None,
                       extra: Optional[Dict] = None,
                       peaks_of: Optional[str] = None
                       ) -> AttributionReport:
    """Build the attribution report for one compiled program.

    The roofline and the MFU are taken against the published peaks of the
    device kind ``peaks_of`` — by default the kind of the device the
    process runs on.  A kind with no entry in ``costmodel.CHIP_PEAKS`` (a
    CPU, say) gets the counts and no roofline, shares or MFU.

    ``measured_step_s`` anchors the roofline shares and MFU; when None
    the telemetry ``train.step_seconds`` histogram is consulted (armed
    runs), else the report is static-only.  ``ring_n`` is the all-reduce
    replica-group extent (the dp degree on dp×tp meshes) for the wire
    model.  ``mesh`` (a Mesh or MeshSpec) adds the per-axis collective
    byte breakdown to the report's collective section — replica traffic
    becomes directly attributable to dp/tp/sp/ep/pp.  ``hlo_text`` skips
    the ``as_text()`` call when the caller already has the dump."""
    from ..analysis import costmodel
    from ..parallel import audit

    if hlo_text is None:
        hlo_text = compiled.as_text()
    ring_n = ring_n or n_devices

    fl = costmodel.analytic_flops(hlo_text)
    per_class = costmodel.instruction_bytes(hlo_text)
    dtype_split = costmodel.bytes_by_dtype(per_class)
    # memory plane: costmodel entry-signature prediction reconciled
    # against the compiled memory_analysis(), plus the measured live/
    # peak gauges when the memory plane is armed
    io_pred = costmodel.entry_io_bytes(hlo_text)
    mem_compiled = costmodel.memory_breakdown(compiled)
    memory_section: Dict = {
        "predicted": dict(io_pred,
                          peak_bytes=io_pred["argument_bytes"]
                          + io_pred["output_bytes"]),
    }
    if mem_compiled:
        memory_section["compiled"] = mem_compiled
        denom = (mem_compiled["argument_bytes"]
                 + mem_compiled["output_bytes"])
        pred = io_pred["argument_bytes"] + io_pred["output_bytes"]
        memory_section["predicted_vs_compiled"] = (
            round(pred / denom, 4) if denom else None)
    from . import memory as _memory
    measured_mem = _memory.measured_snapshot()
    if measured_mem:
        memory_section["measured"] = measured_mem
    _memory.note_program(name, breakdown=mem_compiled or None)
    acct = audit.collective_accounting(
        hlo_text, mesh=getattr(mesh, "mesh", mesh))
    wire = 0
    for kind, info in acct.items():
        wire += audit.collective_wire_bytes(kind, info["bytes"], ring_n)
    # per-axis payload rollup (dp vs tp vs ep ... traffic) when the mesh
    # is known — the report-level face of the audit's by_axis accounting
    by_axis: Dict[str, int] = {}
    for info in acct.values():
        for axis, slot in (info.get("by_axis") or {}).items():
            by_axis[axis] = by_axis.get(axis, 0) + int(slot["bytes"])
    overlap = costmodel.collective_compute_overlap(hlo_text)

    cost = _cost_analysis(compiled)
    hlo_flops = cost.get("flops")
    bytes_accessed = cost.get("bytes accessed")
    hlo_cost = {}
    if hlo_flops:
        hlo_cost["flops"] = float(hlo_flops)
        hlo_cost["flops_ratio_analytic_vs_hlo"] = round(
            fl["flops"] / float(hlo_flops), 4) if hlo_flops else None
    if bytes_accessed:
        hlo_cost["bytes_accessed"] = float(bytes_accessed)

    if measured_step_s is None and host_s is None and device_s is None:
        measured_step_s, host_s, device_s = _measured_from_telemetry()

    import jax
    devs = jax.devices()
    peaks_of = peaks_of or devs[0].device_kind
    # HBM roofline prefers XLA's deduplicated traffic number; the
    # instruction-byte table is the per-class breakdown, not the roof
    instr_total = sum(b for dts in per_class.values()
                      for b in dts.values())
    hbm_bytes = float(bytes_accessed) if bytes_accessed else \
        float(instr_total)
    try:
        peaks = costmodel.chip_peaks(peaks_of)
    except ValueError as e:
        peaks = None
        roof = {"bound": "unknown", "no_peaks": str(e)}
    else:
        roof = costmodel.roofline(fl["flops"], hbm_bytes, float(wire),
                                  peaks, measured_step_s=measured_step_s)
        roof["peaks_of"] = peaks_of

    step: Dict = {}
    if measured_step_s:
        # ns precision: toy programs step in the sub-microsecond range
        # and a 6-digit round would zero them out (killing conformance)
        step["measured_s"] = round(float(measured_step_s), 9)
        if peaks:
            step["mfu"] = round(fl["flops"] / measured_step_s
                                / peaks["flops"], 6)
    if host_s is not None:
        step["host_enqueue_s"] = round(float(host_s), 9)
    if device_s is not None:
        step["device_wait_s"] = round(float(device_s), 9)
    if measured_step_s and host_s is not None:
        step["host_share"] = round(float(host_s) / measured_step_s, 4)

    # input-bound verdict (ROADMAP item 4): the io span p50 vs the step
    # p50 — overrides the device roofline's bound when fetch dominates,
    # because no amount of on-chip optimisation helps a starved step
    try:
        iv = input_verdict(step_s=measured_step_s)
    except Exception:
        iv = None
    if iv:
        roof["input_share"] = iv["input_share"]
        step["io_s"] = iv["io_s"]
        if iv["bound_input"]:
            roof["bound"] = "input"

    topo = {"n_devices": int(n_devices), "ring_n": int(ring_n),
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind}

    data = {
        "kind": "attribution_report",
        "program": name,
        "time": time.time(),
        "topology": topo,
        "analytic": {
            "flops": fl["flops"],
            "transcendentals": fl["transcendentals"],
            "flops_by_op": fl["by_op"],
            "instruction_bytes": per_class,
            "instruction_bytes_total": float(instr_total),
            "bytes_by_dtype": dtype_split,
            "top_contributors": costmodel.top_contributors(per_class),
            "collectives": acct,
            "collectives_by_axis": by_axis,
            "collective_wire_bytes": int(wire),
        },
        "hlo_cost": hlo_cost,
        "overlap": overlap,
        "roofline": roof,
        "step": step,
        "memory": memory_section,
    }
    if extra:
        data.update(extra)

    # conformance vs the budget of record (predict.py): only possible
    # with a measured step; exported per-metric as the
    # perf.conformance{entry,metric} gauge family so dashboards and the
    # heartbeat digest column see drift without parsing reports
    try:
        from ..analysis import predict as _predict
        conf = _predict.runtime_conformance(name, data)
    except Exception:
        logging.debug("conformance pass failed for %s", name,
                      exc_info=True)
        conf = None
    if conf:
        data["conformance"] = conf
        try:
            from . import registry as _registry
            for metric, info in conf["metrics"].items():
                _registry.set_gauge("perf.conformance", info["ratio"],
                                    entry=name, metric=metric)
        except Exception:
            pass
    return AttributionReport(data)


def attribute_fn(fn, *args, name: str = "", n_devices: int = 1,
                 **kwargs) -> AttributionReport:
    """Jit-compile ``fn`` with example args and attribute the result
    (ring/pipeline/moe-style callables; one extra compile)."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    return attribute_compiled(compiled, name or getattr(fn, "__name__",
                                                        "fn"),
                              n_devices=n_devices, **kwargs)


def attribute_module(module) -> AttributionReport:
    """Attribute a bound Module's fused forward program (the
    executor-path entry point; mirrors graphcheck.check_executor)."""
    import jax
    executor = module._exec_group.execs[0]
    prog = executor._prog
    args = tuple(a._handle for a in executor.arg_arrays)
    aux = tuple(a._handle for a in executor.aux_arrays)
    keys = executor._keys()
    fwd = prog._jit_forward(bool(module.for_training))
    compiled = jax.jit(fwd).lower(args, aux, keys).compile()
    return attribute_compiled(
        compiled, "Module(%s)" % (executor._symbol.name or "symbol"))


# ---------------------------------------------------------------------------
# gated entry-point hooks (never raise into the caller)
# ---------------------------------------------------------------------------

def _write(report: AttributionReport, name: str) -> str:
    d = report_dir()
    os.makedirs(d, exist_ok=True)
    _SEQ[0] += 1
    safe = "".join(ch if (ch.isalnum() or ch in "._-") else "_"
                   for ch in name)
    path = os.path.join(d, "attribution-%s-%d-%d.json"
                        % (safe, os.getpid(), _SEQ[0]))
    report.save(path)
    from .. import profiler
    if profiler.is_running():
        for ev in report.perfetto_counters():
            profiler.record_counter(ev["name"], ev["args"], ts_us=ev["ts"])
    return path


def _once(name: str, signature) -> bool:
    key = (name, signature)
    with _DONE_LOCK:
        if key in _DONE:
            return False
        _DONE.add(key)
        return True


def maybe_attribute(compiled, name: str, **kwargs) -> Optional[str]:
    """Gated hook for entry points that already hold a Compiled: write
    one report per program name into the forensics dir.  Returns the
    path, or None (disabled / already done / attribution failed —
    failures are logged, never raised)."""
    if not enabled() or not _once(name, None):
        return None
    try:
        rep = attribute_compiled(compiled, name, **kwargs)
        path = _write(rep, name)
        logging.info("attribution report for %s: %s", name, path)
        return path
    except Exception:
        logging.exception("attribution failed for %s (continuing)", name)
        return None


def maybe_attribute_fn(fn, args, name: str, **kwargs) -> Optional[str]:
    """Gated hook for callable entry points (ring/pipeline/moe): compile
    once per (name, input signature) and write the report."""
    if not enabled():
        return None
    try:
        import jax
        sig = tuple((tuple(x.shape), str(x.dtype))
                    for x in jax.tree_util.tree_leaves(args)
                    if hasattr(x, "shape"))
        if not _once(name, sig):
            return None
        rep = attribute_fn(fn, *args, name=name, **kwargs)
        path = _write(rep, name)
        logging.info("attribution report for %s: %s", name, path)
        return path
    except Exception:
        logging.exception("attribution failed for %s (continuing)", name)
        return None


def maybe_attribute_module(module) -> Optional[str]:
    """Gated hook for ``Module.bind`` (one report per bound symbol +
    shape set)."""
    if not enabled():
        return None
    try:
        executor = module._exec_group.execs[0]
        name = "Module(%s)" % (executor._symbol.name or "symbol")
        sig = tuple(tuple(a.shape) for a in executor.arg_arrays)
        if not _once(name, sig):
            return None
        rep = attribute_module(module)
        path = _write(rep, name)
        logging.info("attribution report for %s: %s", name, path)
        return path
    except Exception:
        logging.exception("attribution failed for Module.bind "
                          "(continuing)")
        return None


def reset_attributed():
    """Forget the attributed-programs memo (tests)."""
    with _DONE_LOCK:
        _DONE.clear()


# ---------------------------------------------------------------------------
# the report's compact form
# ---------------------------------------------------------------------------

def phases_block(report: AttributionReport,
                 report_path: Optional[str] = None) -> Dict:
    """The compact ``phases`` block of a report, for a JSON result
    line: roofline shares, MFU, overlap, and where the full report
    lives."""
    d = report.to_dict()
    roof = d.get("roofline", {})
    shares = roof.get("shares", {})
    out = {
        "bound": roof.get("bound"),
        "compute_share": shares.get("compute"),
        "hbm_share": shares.get("hbm"),
        "collective_share": shares.get("collective"),
        "host_share": shares.get("host"),
        "measured_vs_analytic": roof.get("measured_vs_analytic"),
        "mfu": d.get("step", {}).get("mfu"),
        "overlap_pct": d.get("overlap", {}).get("overlap_pct"),
    }
    mem = d.get("memory", {})
    peak = (mem.get("compiled") or {}).get("peak_bytes") \
        or (mem.get("predicted") or {}).get("peak_bytes")
    if peak:
        out["peak_hbm_bytes"] = int(peak)
    wire = d.get("analytic", {}).get("collective_wire_bytes")
    if wire is not None:
        # per-device wire bytes per step: recorded in the ledger extras
        # (ungated, like peak_hbm_bytes) so wire-traffic trends are
        # tracked without an improvement ever reading as a regression
        out["collective_bytes_per_step"] = int(wire)
    if roof.get("input_share") is not None:
        out["input_share"] = roof["input_share"]
    conf = d.get("conformance")
    if conf:
        out["conformance"] = conf.get("verdict")
        st = (conf.get("metrics") or {}).get("step_time_s")
        if st:
            out["conformance_step_ratio"] = st["ratio"]
    if report_path:
        out["report"] = report_path
    return out
