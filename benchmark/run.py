#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on a machine that holds the chips the cell
asks for.  Everything about a cell is data found by name: its entry in
BENCHMARK.json, ``configs/<config>.json`` (the entry's ``file``),
``traffic/<traffic>.json`` (which names its driver), ``workloads/<cell>.json``
(the limits of ``correct``) and ``metrics/<metric>.py`` (one reader each).
The last line of standard output is the result object; each number compared
for ``correct`` is printed beside its limit at the end of standard error.
"""
import argparse
import gc
import json
import os
import sys
import time
from types import SimpleNamespace as Context  # what a driver is given

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_cell(bench_file, name):
    from benchmark.lib import harness
    bench = harness.load_json(bench_file)
    root = os.path.dirname(os.path.abspath(bench_file))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit("no workload %r in %s (has %s)"
                         % (name, bench_file, sorted(cells)))
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    files = harness.Files(root, bench["paths"])
    return bench, cell, files, {
        "cfg": harness.load_json(os.path.join(root, config["file"])),
        "traffic": files.json("traffic", cell["traffic"]),
        "limits": files.json("workloads", cell["name"])["limits"],
    }


def log(err, what):
    print("[%7.1fs] %s" % (time.perf_counter() - T_START, what), file=err,
          flush=True)


def metrics_of(bench, section, cell_name):
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]


def execute(bench_file, workload, seed, seconds, trace, devices,
            driver_class=None, err=sys.stderr, peaks_for_tests=None):
    """One run on ``devices`` (the platform was checked by the caller).
    Returns the result object.  ``driver_class`` (tests only) takes the
    cell's Driver class and returns the one to run: a subclass with a fault
    planted under the timed path."""
    from benchmark.lib import harness, peaks
    from benchmark.lib import trace as trace_lib

    bench, cell, files, data = load_cell(bench_file, workload)
    devices = list(devices)[:cell["chips"]]
    compiles = harness.Compiles()
    ctx = Context(files=files, seed=int(seed), devices=devices,
                  spans=harness.Spans(), root=ROOT, cell=cell, **data)
    Driver = files.module("drivers", ctx.traffic["driver"]).Driver
    driver = (driver_class(Driver) if driver_class else Driver)(ctx)
    log(err, "set-up of %s, seed %s" % (workload, seed))
    driver.setup()
    log(err, "set-up done; window of %s s" % seconds)
    setup_compiles = (compiles.programs, compiles.cache_hits,
                      compiles.seconds)
    facts = {"setup_s": time.perf_counter() - T_START}
    facts.update(driver.window(float(seconds)))
    facts.update({"cfg": ctx.cfg, "traffic": ctx.traffic,
                  "chips": len(devices),
                  "compile_s": setup_compiles[2],
                  "compiled_in_window": compiles.programs - setup_compiles[0]})
    log(err, "window closed: %d attempted in %.3f s; %s"
        % (facts["attempted"], facts["window_s"],
           json.dumps(facts.get("engine") or {"steps": facts.get("steps")})))
    section = "per_layer" if trace else "end_to_end"
    events = None
    device = {}
    if trace:
        tdir = harness.trace_dir(ROOT)
        import jax.profiler
        with harness.profiler_window(tdir):
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
                facts["traced"] = driver.traced_segment(
                    float(ctx.traffic["trace_seconds"]))
        host_names = (trace_lib.WINDOW_SPAN,) + tuple(ctx.spans.seconds) \
            + tuple(facts["traced"].get("host_seconds", ()))
        events = trace_lib.load_events(trace_lib.find_xplane(tdir),
                                       host_names=host_names)
        window = trace_lib.window_of(events)
        if window:      # busy and window on the trace's own clock
            facts["traced"]["window_s"] = (window[1] - window[0]) / 1e9
        events = facts["events"] = trace_lib.clip_to_window(events)
        device["busy_s"] = trace_lib.busy_seconds(events)
        device["window_s"] = facts["traced"]["window_s"]
    device = dict(harness.device_block(devices), **device)
    log(err, "memory_stats %s; the timed program's plan %s bytes"
        % (json.dumps(devices[0].memory_stats() or {}),
           getattr(driver, "plan_bytes", None)))
    facts["peaks"] = peaks_for_tests or peaks.chip_peaks(device["kind"])
    driver.release()
    gc.collect()
    log(err, "program released; reference")
    checked = driver.verify()
    log(err, "reference done")
    correct = (all(v <= lim for _n, v, lim, _w in checked)
               and facts["failed"] == 0 and facts["compiled_in_window"] == 0)
    compared = {n: {"value": v, "limit": lim} for n, v, lim, _w in checked}
    compared["compiled_in_window"] = {"value": facts["compiled_in_window"],
                                      "limit": 0}

    metrics = {}
    for m in metrics_of(bench, section, cell["name"]):
        value = files.module("metrics", m["name"]).read(facts)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": facts["attempted"],
              "failed": facts["failed"], "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = {"device_ops": trace_lib.top_ops(events),
                               "idle_gaps": trace_lib.idle_gaps(events)}
    result["setup"] = {"programs": setup_compiles[0],
                       "from_cache": setup_compiles[1],
                       "compile_s": setup_compiles[2]}
    result["compared"] = compared
    for n, v, lim, where in checked:
        print("compared %s = %.6g (limit %.6g)%s"
              % (n, v, lim, " at %s" % where if where else ""), file=err)
    print("compared compiled_in_window = %d (limit 0); failed = %d of %d; "
          "correct = %s" % (facts["compiled_in_window"], facts["failed"],
                            facts["attempted"], bool(correct)), file=err)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench_file = os.path.join(ROOT, "BENCHMARK.json")
    _bench, cell, _files, _data = load_cell(bench_file, args.workload)
    try:
        import mxnet_tpu  # noqa: F401  the program; it places jax's cache
    except ImportError as e:
        print("benchmark: the program is not beside the benchmark (%s); "
              "nothing was run" % e, file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("benchmark: %s needs %d TPU chip(s); jax.devices() = %s; "
              "nothing was run" % (args.workload, cell["chips"], devices),
              file=sys.stderr)
        return 2
    result = execute(bench_file, args.workload, args.seed, args.seconds,
                     bool(args.trace), devices)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
