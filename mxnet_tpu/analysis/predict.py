"""Prediction-conformance plane: calibrated pre-flight budgets and
runtime drift verdicts.

The analytic cost model (:mod:`costmodel`) validates hard — collective
bytes at 1.000x, FLOPs/bytes within the 5% CI gate, memory reconciling
at 1.00 — but a roofline lower bound is not a *prediction*: real steps
land above the device roof by a hardware- and program-class-dependent
achievable fraction.  This module closes that gap in three pieces:

* **calibration store** — achievable-fraction coefficients per
  ``device_kind × roofline bucket`` (compute / hbm / collective),
  fitted from the telemetry step histograms (every attribution report
  with a measured step is a calibration sample).  Persisted under the
  PR-13 shared cache rule
  (:func:`~mxnet_tpu.compile.paths.cache_location`):
  ``MXNET_TPU_CALIBRATION_CACHE`` overrides, off-values disable, default
  ``<checkout>/.cache/calibration.json``.

* **pre-flight budgets** — :func:`predict_budget` composes the cost
  model's FLOPs / HBM bytes / per-axis collective wire / memory
  breakdown with the calibrated fraction into predicted step-time,
  peak-HBM, wire-bytes and throughput budgets, gated against
  ``MXNET_TPU_DEVICE_HBM_GB``-style limits.  ``tpulint --predict``
  runs it over the standard entry points and writes atomic
  ``predict-*.json`` reports into the forensics dir.

* **runtime conformance** — :func:`conformance` compares measured
  histograms against a budget and hands back per-metric
  measured/predicted ratios with a WITHIN / DEGRADED / VIOLATED
  verdict; the bands are sized by the history's drawdown-σ
  (``max(σ·noise, floor)`` with the floor at the ~20% agreement
  target).  ``telemetry/perf.py`` folds the section into attribution
  reports, exports ``perf.conformance{entry,metric}`` gauges and a
  Perfetto counter track, and the heartbeat digests carry a per-rank
  conformance column so the fleet view can finger a rank slow against
  its OWN budget, not just against its peers.

"A Learned Performance Model for TPUs" (PAPERS.md) is the blueprint:
a calibrated per-hardware predictor is the prerequisite for every
downstream decision — ROADMAP item 1(b–d) consumes exactly this store.
"""
from __future__ import annotations

import json
import os
import statistics
import threading
import time
from typing import Dict, List, Optional

from ..compile.paths import cache_location

__all__ = ["DEFAULT_FRACTION", "achievable_fraction", "budget_table",
           "calibration_store_path", "conformance", "conformance_bands",
           "digest_column", "fit_from_attribution",
           "load_store", "note_budget", "noted_budget", "predict_budget",
           "predict_decode_budget", "reset", "runtime_conformance",
           "save_report", "save_store", "update_calibration"]

STORE_VERSION = 1
ENV_STORE = "MXNET_TPU_CALIBRATION_CACHE"

# the chip every budget is a prediction FOR, whichever host computes it
# (a pre-flight check runs before the job reaches its chip); its peaks
# come from costmodel.CHIP_PEAKS and ride in the report's basis
TARGET_DEVICE_KIND = "TPU v5 lite"

# uncalibrated fallback: a real step typically lands near half its
# device roof (host residue, launch gaps, un-overlapped collectives) —
# honest enough to bootstrap, replaced by the first fitted sample
DEFAULT_FRACTION = 0.5

# conformance floor = the repo's ~20% prediction-agreement target; the
# σ multiplier sizes the band by the history's own noise
CONFORMANCE_FLOOR = 0.20
SIGMA_MULT = 4.0

VERDICTS = ("WITHIN", "DEGRADED", "VIOLATED")

_SEQ = [0]
_LOCK = threading.Lock()
_NOTED: Dict[str, Dict] = {}            # program -> budget of record
_LAST_CONFORMANCE: Dict[str, Dict] = {}  # program -> conformance section


# ---------------------------------------------------------------------------
# calibration store
# ---------------------------------------------------------------------------

def calibration_store_path() -> Optional[str]:
    """On-disk location of the calibration store (PR-13 shared cache
    rule); None when ``MXNET_TPU_CALIBRATION_CACHE`` disables it."""
    return cache_location(ENV_STORE, "calibration.json")


def device_kind() -> str:
    try:
        import jax
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def _empty_store() -> Dict:
    return {"version": STORE_VERSION, "fitted_t": None, "entries": {}}


def load_store(path: Optional[str] = None) -> Dict:
    """Read the persisted store (an empty one when missing/disabled/
    corrupt — a broken cache must never break a run)."""
    path = calibration_store_path() if path is None else path
    if not path or not os.path.isfile(path):
        return _empty_store()
    try:
        with open(path) as f:
            store = json.load(f)
    except (OSError, ValueError):
        return _empty_store()
    if not isinstance(store, dict) or \
            not isinstance(store.get("entries"), dict):
        return _empty_store()
    return store


def save_store(store: Dict, path: Optional[str] = None) -> Optional[str]:
    """Atomic write (tmp + fsync + replace); no-op when disabled."""
    path = calibration_store_path() if path is None else path
    if not path:
        return None
    store = dict(store, version=STORE_VERSION, fitted_t=time.time())
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(store, f, indent=2, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def _key(kind: str, bucket: str) -> str:
    return "%s|%s" % (kind, bucket)


def update_calibration(store: Dict, kind: str, bucket: str,
                       fraction: float, source: str = "measured",
                       weight: int = 1) -> Dict:
    """Fold one achievable-fraction sample into the store entry for
    ``device_kind × bucket`` (running mean over sample count).  The
    fraction is clamped to [1e-4, 1]: a step can never beat its roof,
    and the low end still admits hosts (CPU dev boxes) whose real
    throughput sits far under the modeled accelerator peaks."""
    fraction = min(1.0, max(1e-4, float(fraction)))
    ent = store["entries"].get(_key(kind, bucket))
    if ent is None:
        ent = {"achievable_fraction": fraction, "n": int(weight),
               "source": source}
    else:
        n = int(ent.get("n", 1))
        total = ent["achievable_fraction"] * n + fraction * weight
        n += int(weight)
        ent = {"achievable_fraction": round(total / n, 6), "n": n,
               "source": source if source == ent.get("source")
               else "mixed"}
    store["entries"][_key(kind, bucket)] = ent
    return store


def achievable_fraction(store: Optional[Dict], kind: str,
                        bucket: str) -> Dict:
    """``{"fraction", "n", "source"}`` for a device_kind × roofline
    bucket; falls back to the same device's other buckets' mean, then to
    :data:`DEFAULT_FRACTION` (``source: "default"``)."""
    store = store or _empty_store()
    ent = store["entries"].get(_key(kind, bucket))
    if ent:
        return {"fraction": float(ent["achievable_fraction"]),
                "n": int(ent.get("n", 1)),
                "source": ent.get("source", "measured")}
    same_kind = [e["achievable_fraction"]
                 for k, e in store["entries"].items()
                 if k.startswith(kind + "|")]
    if same_kind:
        return {"fraction": round(statistics.fmean(same_kind), 6),
                "n": 0, "source": "nearest-bucket"}
    return {"fraction": DEFAULT_FRACTION, "n": 0, "source": "default"}


def fit_from_attribution(store: Dict, data: Dict) -> Optional[Dict]:
    """One calibration sample from an attribution report (or its data
    dict): achievable fraction = device_roof_s / measured step, bucketed
    by the DEVICE binding roof (host/input verdicts calibrate the bucket
    the device math picked, not themselves)."""
    roof = (data.get("roofline") or {})
    step = (data.get("step") or {})
    measured = step.get("measured_s")
    device_roof = roof.get("device_roof_s")
    if not measured or not device_roof:
        return None
    comp = {"compute": roof.get("compute_s", 0.0),
            "hbm": roof.get("hbm_s", 0.0),
            "collective": roof.get("collective_s", 0.0)}
    bucket = max(comp, key=comp.get)
    kind = ((data.get("topology") or {}).get("device_kind")
            or device_kind())
    return update_calibration(store, kind, bucket,
                              device_roof / measured, source="telemetry")


# ---------------------------------------------------------------------------
# pre-flight budgets
# ---------------------------------------------------------------------------

def budget_limits() -> Dict[str, float]:
    """Operator-declared ceilings the pre-flight gate checks budgets
    against (absent env -> metric not gated):

    * ``MXNET_TPU_DEVICE_HBM_GB``       peak-HBM ceiling (the memory
      plane's capacity override — ONE knob for GC501 and the budget)
    * ``MXNET_TPU_STEP_BUDGET_MS``      predicted-step ceiling
    * ``MXNET_TPU_WIRE_BUDGET_MB``      per-step collective wire ceiling
    * ``MXNET_TPU_THROUGHPUT_FLOOR``    items/s floor (a budget BELOW
      this is over budget)
    """
    out = {}

    def envf(name):
        try:
            return float(os.environ[name])
        except (KeyError, ValueError):
            return None

    v = envf("MXNET_TPU_DEVICE_HBM_GB")
    if v:
        out["peak_hbm_bytes"] = v * (1 << 30)
    v = envf("MXNET_TPU_STEP_BUDGET_MS")
    if v:
        out["step_time_s"] = v / 1e3
    v = envf("MXNET_TPU_WIRE_BUDGET_MB")
    if v:
        out["wire_bytes_per_step"] = v * 1e6
    v = envf("MXNET_TPU_THROUGHPUT_FLOOR")
    if v:
        out["throughput_per_s"] = v
    return out


def _gate(budget: Dict, limits: Dict) -> List[str]:
    over = []
    for metric, limit in limits.items():
        v = budget.get(metric)
        if v is None:
            continue
        if metric == "throughput_per_s":
            if v < limit:
                over.append(metric)
        elif v > limit:
            over.append(metric)
    return over


def predict_budget(compiled=None, name: str = "program", *,
                   n_devices: int = 1, ring_n: Optional[int] = None,
                   hlo_text: Optional[str] = None, mesh=None,
                   items_per_step: Optional[float] = None,
                   store: Optional[Dict] = None) -> Dict:
    """The pre-flight budget for one program: cost-model features ×
    calibrated achievable fraction -> predicted step-time / peak-HBM /
    wire-bytes / throughput, gated against :func:`budget_limits`.

    ``compiled`` (when given) supplies XLA's deduplicated
    bytes-accessed and the compiled memory breakdown; ``hlo_text``
    alone runs the pure-static path.  The report is remembered as the
    program's budget of record (:func:`note_budget`) so the runtime
    conformance pass compares against exactly what was promised."""
    from . import costmodel
    from ..parallel import audit

    if hlo_text is None:
        hlo_text = compiled.as_text()
    ring_n = ring_n or n_devices

    fl = costmodel.analytic_flops(hlo_text)
    per_class = costmodel.instruction_bytes(hlo_text)
    instr_total = float(sum(b for dts in per_class.values()
                            for b in dts.values()))
    bytes_accessed = None
    if compiled is not None:
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            bytes_accessed = float(ca.get("bytes accessed") or 0) or None
        except Exception:
            bytes_accessed = None
    hbm_bytes = bytes_accessed if bytes_accessed else instr_total

    acct = audit.collective_accounting(hlo_text,
                                       mesh=getattr(mesh, "mesh", mesh))
    wire = 0
    for kind_name, info in acct.items():
        wire += audit.collective_wire_bytes(kind_name, info["bytes"],
                                            ring_n)

    roof = costmodel.roofline(fl["flops"], hbm_bytes, float(wire),
                              costmodel.chip_peaks(TARGET_DEVICE_KIND))
    kind = device_kind()
    store = load_store() if store is None else store
    cal = achievable_fraction(store, kind, roof["bound"])
    step_s = (roof["device_roof_s"] / cal["fraction"]
              if roof["device_roof_s"] > 0 else None)

    peak = None
    if compiled is not None:
        peak = costmodel.memory_breakdown(compiled).get("peak_bytes")
    if not peak:
        io = costmodel.entry_io_bytes(hlo_text)
        peak = io["argument_bytes"] + io["output_bytes"]

    budget = {
        "step_time_s": round(step_s, 9) if step_s else None,
        "peak_hbm_bytes": int(peak),
        "wire_bytes_per_step": int(wire),
        "throughput_per_s": round(items_per_step / step_s, 3)
        if (items_per_step and step_s) else None,
    }
    limits = budget_limits()
    report = {
        "kind": "predict_report",
        "program": name,
        "time": time.time(),
        "topology": {"n_devices": int(n_devices), "ring_n": int(ring_n),
                     "device_kind": kind},
        "budget": budget,
        "basis": {
            "flops": fl["flops"],
            "hbm_bytes": float(hbm_bytes),
            "hbm_basis": "cost_analysis" if bytes_accessed
            else "instruction_bytes",
            "device_roof_s": roof["device_roof_s"],
            "compute_s": roof["compute_s"],
            "hbm_s": roof["hbm_s"],
            "collective_s": roof["collective_s"],
            "bound": roof["bound"],
            "peaks": roof["peaks"],
            "achievable_fraction": cal["fraction"],
            "calibration_source": cal["source"],
            "calibration_n": cal["n"],
            "items_per_step": items_per_step,
        },
        "limits": limits,
        "over_budget": _gate(budget, limits),
    }
    note_budget(name, report)
    return report


def predict_decode_budget(num_layers: int, hidden: int, vocab: int,
                          slots: int, cached_tokens: int,
                          quant_bits: int = 32, name: str = "decode",
                          store: Optional[Dict] = None) -> Dict:
    """Decode-entry budget from :func:`costmodel.decode_step_model`
    (weights-bandwidth-bound: no HLO needed) — throughput budget is
    tokens/s across all ``slots``."""
    from . import costmodel

    model = costmodel.decode_step_model(num_layers, hidden, vocab, slots,
                                        cached_tokens,
                                        quant_bits=quant_bits)
    roof = costmodel.roofline(model["flops"], model["hbm_bytes"], 0.0,
                              costmodel.chip_peaks(TARGET_DEVICE_KIND))
    kind = device_kind()
    store = load_store() if store is None else store
    cal = achievable_fraction(store, kind, roof["bound"])
    step_s = (roof["device_roof_s"] / cal["fraction"]
              if roof["device_roof_s"] > 0 else None)
    budget = {
        "step_time_s": round(step_s, 9) if step_s else None,
        "peak_hbm_bytes": int(model["hbm_bytes"]),
        "wire_bytes_per_step": 0,
        "throughput_per_s": round(slots / step_s, 3) if step_s else None,
    }
    limits = budget_limits()
    report = {
        "kind": "predict_report",
        "program": name,
        "time": time.time(),
        "topology": {"n_devices": 1, "ring_n": 1, "device_kind": kind},
        "budget": budget,
        "basis": dict(model, bound=roof["bound"],
                      device_roof_s=roof["device_roof_s"],
                      achievable_fraction=cal["fraction"],
                      calibration_source=cal["source"],
                      calibration_n=cal["n"], items_per_step=slots),
        "limits": limits,
        "over_budget": _gate(budget, limits),
    }
    note_budget(name, report)
    return report


def save_report(report: Dict) -> str:
    """Atomic ``predict-<program>-<pid>-<seq>.json`` into the same
    forensics dir as attribution reports and preflight post-mortems."""
    from ..telemetry import perf as _perf
    d = _perf.report_dir()
    os.makedirs(d, exist_ok=True)
    with _LOCK:
        _SEQ[0] += 1
        seq = _SEQ[0]
    safe = "".join(ch if (ch.isalnum() or ch in "._-") else "_"
                   for ch in report.get("program", "program"))
    path = os.path.join(d, "predict-%s-%d-%d.json"
                        % (safe, os.getpid(), seq))
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True, default=repr)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def budget_table(reports: List[Dict]) -> str:
    """The pretty budget table ``tpulint --predict`` prints."""
    lines = ["%-14s %-10s %10s %10s %10s %12s %-16s %s"
             % ("entry", "bound", "step_ms", "hbm_MB", "wire_MB",
                "items/s", "calibration", "verdict")]
    for r in reports:
        b = r.get("budget", {})
        basis = r.get("basis", {})
        over = r.get("over_budget") or []
        lines.append(
            "%-14s %-10s %10s %10.2f %10.3f %12s %-16s %s"
            % (r.get("program", "?")[:14], basis.get("bound", "?"),
               ("%.4g" % (1e3 * b["step_time_s"]))
               if b.get("step_time_s") else "-",
               (b.get("peak_hbm_bytes") or 0) / 1e6,
               (b.get("wire_bytes_per_step") or 0) / 1e6,
               ("%.1f" % b["throughput_per_s"])
               if b.get("throughput_per_s") else "-",
               "%s n=%s f=%.2f" % (basis.get("calibration_source", "?"),
                                   basis.get("calibration_n", 0),
                                   basis.get("achievable_fraction", 0.0)),
               ("OVER BUDGET: " + ",".join(over)) if over else "ok"))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# conformance: measured vs budget
# ---------------------------------------------------------------------------

def note_budget(program: str, report: Dict) -> None:
    """Remember a program's budget of record (runtime conformance
    compares against it; latest note wins)."""
    with _LOCK:
        _NOTED[program] = report


def noted_budget(program: str) -> Optional[Dict]:
    with _LOCK:
        return _NOTED.get(program)


def _drawdown_sigma(history: List[float]) -> float:
    """Standard deviation of a series' relative drawdowns from its
    running maximum: the noise the conformance bands are sized by."""
    if len(history) < 2:
        return 0.0
    run_max = history[0]
    draws = []
    for v in history[1:]:
        run_max = max(run_max, v)
        draws.append((run_max - v) / run_max if run_max > 0 else 0.0)
    if len(draws) < 2:
        return 0.0
    return statistics.stdev(draws)


def conformance_bands(history: Optional[List[float]] = None,
                      floor: float = CONFORMANCE_FLOOR,
                      sigma_mult: float = SIGMA_MULT) -> Dict:
    """Verdict bands for one metric: DEGRADED past ``max(σ·noise,
    floor)`` in the bad direction, VIOLATED past twice that."""
    noise = _drawdown_sigma(history or [])
    tol = max(sigma_mult * noise, floor)
    return {"degraded_tolerance": round(tol, 4),
            "violated_tolerance": round(2 * tol, 4),
            "noise_sigma": round(noise, 4),
            "basis": "sigma" if sigma_mult * noise > floor else "floor"}


_LOWER_IS_BETTER = {"step_time_s": True, "peak_hbm_bytes": True,
                    "wire_bytes_per_step": True,
                    "throughput_per_s": False,
                    "decode_tokens_per_s": False}


def conformance(budget_report: Dict, measured: Dict,
                history_by_metric: Optional[Dict] = None,
                floor: float = CONFORMANCE_FLOOR,
                sigma_mult: float = SIGMA_MULT) -> Optional[Dict]:
    """Per-metric measured/predicted ratios + verdicts against one
    budget.  ``measured`` maps metric names (budget schema keys, plus
    ``decode_tokens_per_s`` which compares against the throughput
    budget) to measured values; metrics without both sides are
    skipped.  None when nothing is comparable."""
    budget = budget_report.get("budget", budget_report)
    metrics = {}
    worst = "WITHIN"
    for metric, meas in measured.items():
        lower = _LOWER_IS_BETTER.get(metric)
        if lower is None or meas is None:
            continue
        budget_key = "throughput_per_s" \
            if metric == "decode_tokens_per_s" else metric
        pred = budget.get(budget_key)
        if not pred:
            continue
        ratio = float(meas) / float(pred)
        badness = (ratio - 1.0) if lower else (1.0 / max(ratio, 1e-9)
                                               - 1.0)
        bands = conformance_bands((history_by_metric or {}).get(metric),
                                  floor=floor, sigma_mult=sigma_mult)
        if badness <= bands["degraded_tolerance"]:
            verdict = "WITHIN"
        elif badness <= bands["violated_tolerance"]:
            verdict = "DEGRADED"
        else:
            verdict = "VIOLATED"
        if VERDICTS.index(verdict) > VERDICTS.index(worst):
            worst = verdict
        metrics[metric] = {"measured": float(meas),
                           "predicted": float(pred),
                           "ratio": round(ratio, 4),
                           "verdict": verdict, "band": bands}
    if not metrics:
        return None
    return {"verdict": worst, "metrics": metrics,
            "budget_program": budget_report.get("program"),
            "calibration_source": (budget_report.get("basis") or {})
            .get("calibration_source")}


def runtime_conformance(program: str, data: Dict,
                        store: Optional[Dict] = None) -> Optional[Dict]:
    """The attribution-time conformance pass (telemetry/perf.py calls
    this once per attributed program, after the warmup):

    * with a noted pre-flight budget: measured step (telemetry p50),
      measured peak HBM (memory plane) and the compiled program's
      audited wire bytes are all held against what was promised;
    * without one: a self-budget is derived from the report's own
      static analytics × the calibrated fraction, and only step time is
      compared (the other metrics would be compared against
      themselves).

    When the run produced a measured step, the sample also refits the
    calibration store (disable with ``MXNET_TPU_CALIBRATION_REFIT=0``).
    """
    step = data.get("step") or {}
    measured_s = step.get("measured_s")
    if not measured_s:
        return None

    store = load_store() if store is None else store
    budget_rep = noted_budget(program)
    measured: Dict[str, float] = {"step_time_s": float(measured_s)}
    if budget_rep is not None:
        mm = (data.get("memory") or {}).get("measured") or {}
        if mm.get("peak_live_bytes"):
            measured["peak_hbm_bytes"] = float(mm["peak_live_bytes"])
        wire = (data.get("analytic") or {}).get("collective_wire_bytes")
        if wire:
            measured["wire_bytes_per_step"] = float(wire)
    else:
        roof = data.get("roofline") or {}
        device_roof = roof.get("device_roof_s")
        if not device_roof:
            return None
        comp = {"compute": roof.get("compute_s", 0.0),
                "hbm": roof.get("hbm_s", 0.0),
                "collective": roof.get("collective_s", 0.0)}
        bucket = max(comp, key=comp.get)
        kind = ((data.get("topology") or {}).get("device_kind")
                or device_kind())
        cal = achievable_fraction(store, kind, bucket)
        budget_rep = {
            "program": program,
            "budget": {"step_time_s": device_roof / cal["fraction"]},
            "basis": {"bound": bucket,
                      "achievable_fraction": cal["fraction"],
                      "calibration_source": cal["source"],
                      "calibration_n": cal["n"]},
        }
    conf = conformance(budget_rep, measured)
    if conf:
        with _LOCK:
            _LAST_CONFORMANCE[program] = conf
    # the sample refits the store only AFTER the budget was derived —
    # calibrating the budget from the very step it judges would make
    # every verdict read WITHIN by construction
    if os.environ.get("MXNET_TPU_CALIBRATION_REFIT", "1") not in (
            "0", "false", "off"):
        try:
            if fit_from_attribution(store, data) is not None:
                save_store(store)
        except Exception:
            pass
    return conf


def digest_column() -> Optional[Dict]:
    """This rank's worst conformance outcome, compact enough for the
    ~200-byte heartbeat digest: ``{"ratio", "verdict", "metric",
    "program"}`` — the fleet view's per-rank budget column."""
    with _LOCK:
        items = list(_LAST_CONFORMANCE.items())
    worst = None
    for program, conf in items:
        for metric, info in (conf.get("metrics") or {}).items():
            lower = _LOWER_IS_BETTER.get(metric, True)
            badness = (info["ratio"] - 1.0) if lower \
                else (1.0 / max(info["ratio"], 1e-9) - 1.0)
            cand = (VERDICTS.index(info["verdict"]), badness,
                    {"ratio": info["ratio"], "verdict": info["verdict"],
                     "metric": metric, "program": program})
            if worst is None or cand[:2] > worst[:2]:
                worst = cand
    return worst[2] if worst else None


def reset() -> None:
    """Forget noted budgets + conformance outcomes (tests)."""
    with _LOCK:
        _NOTED.clear()
        _LAST_CONFORMANCE.clear()
