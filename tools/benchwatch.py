#!/usr/bin/env python3
"""Bench-trajectory ledger + statistical regression gate.

The bench numbers of record (bench.py's JSON line, the driver's
BENCH_r*.json artifacts) accumulate into ONE append-only ledger —
``PERF_LEDGER.jsonl``, one JSON object per bench round — and ``check``
gates new rounds against the trajectory: a drop beyond the noise the
history itself exhibits exits nonzero, so a perf regression fails CI
the same run it lands instead of being noticed three rounds later
(exactly how the r01→r05 plateau went unflagged).

Usage:
    python tools/benchwatch.py append --ledger L --from-bench bench.json
    python tools/benchwatch.py append --ledger L --metric transformer_mfu=0.41
    python tools/benchwatch.py check --ledger L [--json]    # or: --check
    python tools/benchwatch.py show --ledger L

    --ledger PATH   ledger file, required (PERF_LEDGER.jsonl at the repo
                    root is the PR driver's record, not this tool's)
    --sigma N       regression threshold in noise sigmas (default 4)
    --floor F       minimum relative drop to flag regardless of sigma
                    (default 0.05 = 5%: sub-noise-floor trajectories
                    would otherwise flag measurement jitter)

Gate semantics (per metric):  the latest entry is compared against the
best-known value in the history; the noise scale is the sigma of
historical excursions past the running best (drawdowns below the
running max for higher-is-better metrics — improvements are signal,
not noise, and must not widen the band).  A move beyond
``max(sigma * noise, floor)`` in the WRONG direction is a regression.
Most metrics (img/s, tok/s, MFU) are higher-is-better;
``compile_seconds`` (and its ``transformer_`` twin) is gated
LOWER-is-better — a compile-time improvement (a drop) can never read
as a regression, a compile-time blow-up does.  ``append`` accepts
bench.py's raw JSON line or the driver's BENCH_r*.json wrapper
(``{"parsed": {...}}``); bench.py appends automatically when
``BENCH_LEDGER`` names a ledger path.

Ledger entry schema: ``{"t", "source", "metrics": {...}}`` plus an
optional ``"extra"`` block for recorded-but-not-gated fields — today
the memory plane's per-benchmark ``peak_hbm_bytes`` (and
``transformer_peak_hbm_bytes``) lifted from the bench ``phases``
block.  Extras never enter the gate: metrics are higher-is-better, and
a peak-HBM improvement (a drop) must not read as a regression.

Exit status: check → 0 clean, 1 regression(s), 2 unreadable ledger.
"""
import argparse
import json
import os
import statistics
import sys
import time

SIGMA_MULT = 4.0
FLOOR = 0.05


def lower_is_better(name):
    """Metrics gated in the inverted direction (a DROP is the
    improvement): today the compile-time plane's ``compile_seconds``
    (promoted from an ungated extra once the compile cache landed —
    recovery-without-recompilation is a gated property now)."""
    return name.endswith("compile_seconds")


# ---------------------------------------------------------------------------
# ledger I/O
# ---------------------------------------------------------------------------

def extract_metrics(doc):
    """Flat {metric_name: value} from a bench document: bench.py's JSON
    line, or the driver's BENCH_r*.json wrapper carrying it under
    'parsed'."""
    if not isinstance(doc, dict):
        return {}
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        doc = doc["parsed"]
    out = {}
    name = doc.get("metric")
    if name and isinstance(doc.get("value"), (int, float)):
        out[name] = float(doc["value"])
    if isinstance(doc.get("mfu"), (int, float)):
        out[(name or "bench") + "_mfu"] = float(doc["mfu"])
    # compile time is a GATED metric since the compile-cache round
    # (lower-is-better: see lower_is_better()); it was an ungated extra
    # before — metric_series() still folds those legacy extras into the
    # same history
    phases = doc.get("phases")
    if isinstance(phases, dict) and \
            isinstance(phases.get("compile_seconds"), (int, float)):
        out["compile_seconds"] = round(float(phases["compile_seconds"]), 6)
    sub = doc.get("transformer")
    if isinstance(sub, dict):
        for k, v in extract_metrics(sub).items():
            out["transformer_" + k if k == "compile_seconds" else k] = v
    return out


def extract_extra(doc):
    """Recorded-but-not-gated fields from a bench document — the memory
    plane's peak HBM and the collective plane's per-step wire bytes
    (phases.peak_hbm_bytes / phases.collective_bytes_per_step).  These
    land in the ledger entry's ``extra`` block, NOT ``metrics``: the
    gate treats every metric as higher-is-better, and a peak-HBM or
    wire-bytes *improvement* (a drop) must never read as a regression."""
    if not isinstance(doc, dict):
        return {}
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        doc = doc["parsed"]
    out = {}
    phases = doc.get("phases")
    if isinstance(phases, dict):
        for field in ("peak_hbm_bytes", "collective_bytes_per_step"):
            if isinstance(phases.get(field), (int, float)):
                out[field] = int(phases[field])
        # measured/predicted step ratio from the conformance pass:
        # ungated for the same reason — drift toward 1.0 (a better
        # calibration) must never read as a regression
        if isinstance(phases.get("conformance_step_ratio"),
                      (int, float)):
            out["conformance_step_ratio"] = round(
                float(phases["conformance_step_ratio"]), 4)
        # compile_seconds moved from here into extract_metrics when it
        # was promoted to a (lower-is-better) gated metric
    sub = doc.get("transformer")
    if isinstance(sub, dict):
        for k, v in extract_extra(sub).items():
            out["transformer_" + k] = v
    return out


def append_entry(ledger_path, metrics, source="", t=None, extra=None):
    """Append one round to the ledger (plain append: the ledger is an
    event log, each line self-contained).  A round may carry only
    ``extra`` (ungated) fields — audit-level artifacts like the
    MULTICHIP dryrun publish wire-bytes/overlap facts without any
    throughput metric to gate."""
    if not metrics and not extra:
        raise ValueError("no metrics or extras to append")
    entry = {"t": time.time() if t is None else t, "source": source,
             "metrics": {k: float(v) for k, v in metrics.items()}}
    if extra:
        entry["extra"] = extra
    with open(ledger_path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")
    return entry


def read_ledger(path):
    entries = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                e = json.loads(line)
            except ValueError:
                raise ValueError("ledger %s line %d is not JSON"
                                 % (path, i + 1))
            if isinstance(e, dict) and isinstance(e.get("metrics"), dict):
                entries.append(e)
    return entries


def metric_series(entries):
    """{metric: [values in ledger order]} (rounds missing a metric are
    simply absent from that series).  Lower-is-better metrics that
    older rounds recorded in the ungated ``extra`` block (compile
    seconds before its promotion) are folded into the same series, so
    the gate has its full history from day one."""
    out = {}
    for e in entries:
        merged = dict(e["metrics"])
        for k, v in (e.get("extra") or {}).items():
            if lower_is_better(k) and k not in merged:
                merged[k] = v
        for k, v in merged.items():
            if isinstance(v, (int, float)):
                out.setdefault(k, []).append(float(v))
    return out


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def drawdown_sigma(history):
    """Noise scale of a higher-is-better series: the sigma of relative
    drawdowns below the running max.  Improvements are signal and do not
    widen the band; a flat-with-jitter series yields its jitter."""
    if len(history) < 2:
        return 0.0
    run_max = history[0]
    draws = []
    for v in history[1:]:
        run_max = max(run_max, v)
        draws.append((run_max - v) / run_max if run_max > 0 else 0.0)
    if len(draws) < 2:
        # one excursion is a data point, not a noise scale — returning
        # it as sigma let a single bad historical round widen the band
        # 4x; report zero and let the caller's floor take over
        return 0.0
    return statistics.stdev(draws)


def rise_sigma(history):
    """Noise scale of a LOWER-is-better series: the sigma of relative
    rises above the running min — mirror image of drawdown_sigma
    (improvements, i.e. drops, are signal and never widen the band)."""
    if len(history) < 2:
        return 0.0
    run_min = history[0]
    rises = []
    for v in history[1:]:
        run_min = min(run_min, v)
        rises.append((v - run_min) / run_min if run_min > 0 else 0.0)
    if len(rises) < 2:
        # mirror of drawdown_sigma: a lone rise is not a noise scale
        return 0.0
    return statistics.stdev(rises)


def check_series(values, sigma_mult=SIGMA_MULT, floor=FLOOR, lower=False):
    """Gate one metric's trajectory: is the LATEST value a regression
    against the best-known, beyond the history's own noise?  ``lower``
    inverts the direction (best = running MIN, a rise regresses) — so a
    compile-time improvement can never read as a regression and a
    blow-up cannot hide.

    Returns {"checked", "regression", "latest", "best", "drop",
    "threshold", "noise_sigma", "band_basis", "direction"}.
    ``band_basis`` says which side of ``max(sigma*noise, floor)`` won:
    a single-row history has no sigma at all (noise 0.0) and gates on
    the explicit 5% floor — the calibration store reads these series,
    so the one-row edge case is load-bearing, not cosmetic."""
    if len(values) < 2:
        return {"checked": False, "regression": False,
                "n": len(values)}
    history, latest = values[:-1], values[-1]
    if lower:
        best = min(history)
        move = (latest - best) / best if best > 0 else 0.0
        noise = rise_sigma(history)
    else:
        best = max(history)
        move = (best - latest) / best if best > 0 else 0.0
        noise = drawdown_sigma(history)
    threshold = max(sigma_mult * noise, floor)
    return {"checked": True,
            "regression": move > threshold,
            "latest": latest, "best": best,
            "drop": round(move, 4), "threshold": round(threshold, 4),
            "noise_sigma": round(noise, 4), "n": len(values),
            "band_basis": "sigma" if sigma_mult * noise > floor
            else "floor",
            "direction": "lower" if lower else "higher"}


def check_ledger(entries, sigma_mult=SIGMA_MULT, floor=FLOOR):
    """(ok, {metric: verdict}) over every metric series in the ledger."""
    results = {}
    ok = True
    for name, values in sorted(metric_series(entries).items()):
        r = check_series(values, sigma_mult=sigma_mult, floor=floor,
                         lower=lower_is_better(name))
        results[name] = r
        if r["regression"]:
            ok = False
    return ok, results


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _cmd_append(args):
    metrics = {}
    extra = {}
    sources = []
    for path in args.from_bench or []:
        with open(path) as f:
            doc = json.load(f)
        metrics.update(extract_metrics(doc))
        extra.update(extract_extra(doc))
        sources.append(os.path.basename(path))
    for kv in args.metric or []:
        k, _, v = kv.partition("=")
        metrics[k] = float(v)
    for kv in args.extra or []:
        k, _, v = kv.partition("=")
        extra[k] = float(v)
    entry = append_entry(args.ledger, metrics,
                         source=args.source or ",".join(sources),
                         extra=extra or None)
    print(json.dumps(entry, sort_keys=True))
    return 0


def _cmd_check(args):
    try:
        entries = read_ledger(args.ledger)
    except (OSError, ValueError) as e:
        print("benchwatch: %s" % e, file=sys.stderr)
        return 2
    ok, results = check_ledger(entries, sigma_mult=args.sigma,
                               floor=args.floor)
    if args.json:
        print(json.dumps({"ok": ok, "rounds": len(entries),
                          "metrics": results}, indent=2, sort_keys=True))
    else:
        print("benchwatch: %d rounds in %s" % (len(entries), args.ledger))
        for name, r in results.items():
            if not r["checked"]:
                print("  %-48s %d point(s), not gated" % (name, r["n"]))
                continue
            verdict = "REGRESSION" if r["regression"] else "ok"
            word = ("rise" if r.get("direction") == "lower" else "drop")
            print("  %-48s latest %.4g vs best %.4g  %s %.1f%% "
                  "(threshold %.1f%%, noise sigma %.2f%%)  %s"
                  % (name, r["latest"], r["best"], word, 100 * r["drop"],
                     100 * r["threshold"], 100 * r["noise_sigma"],
                     verdict))
        if not ok:
            print("benchwatch: REGRESSION beyond noise — investigate "
                  "before merging (PERF.md workflow)")
    return 0 if ok else 1


def _cmd_show(args):
    try:
        entries = read_ledger(args.ledger)
    except (OSError, ValueError) as e:
        print("benchwatch: %s" % e, file=sys.stderr)
        return 2
    for i, e in enumerate(entries):
        when = time.strftime("%Y-%m-%d %H:%M",
                             time.localtime(e["t"])) if e.get("t") else "-"
        ms = "  ".join("%s=%.4g" % kv for kv in
                       sorted(e["metrics"].items()))
        ex = e.get("extra") or {}
        if ex:
            ms += "  [" + "  ".join("%s=%.4g" % kv
                                    for kv in sorted(ex.items())) + "]"
        print("%3d  %s  %-14s %s" % (i + 1, when, e.get("source") or "-",
                                     ms))
    return 0


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    # `--check` as the first token is an alias for the check command
    if argv and argv[0] == "--check":
        argv[0] = "check"
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("command", choices=["append", "check", "show"])
    ap.add_argument("--ledger", required=True,
                    help="the trajectory file to append to or gate; no "
                         "default (PERF_LEDGER.jsonl at the repo root is "
                         "the PR driver's record, not this tool's)")
    ap.add_argument("--from-bench", action="append", default=[],
                    metavar="JSON")
    ap.add_argument("--metric", action="append", default=[],
                    metavar="NAME=VALUE")
    ap.add_argument("--extra", action="append", default=[],
                    metavar="NAME=VALUE",
                    help="recorded-but-not-gated fields (see the extra "
                         "block note in the module docstring)")
    ap.add_argument("--source", default="")
    ap.add_argument("--sigma", type=float, default=SIGMA_MULT)
    ap.add_argument("--floor", type=float, default=FLOOR)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    return {"append": _cmd_append, "check": _cmd_check,
            "show": _cmd_show}[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
