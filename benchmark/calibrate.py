#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, taken on the chip at
a cell's own size, each held to the cell's own limits.  Not part of a
benchmark run.

    python3 benchmark/calibrate.py --workload <cell> --seeds 101,102,... \
        [--control-seeds 101,102,103] [--seconds 6] [--program-float32]

A training cell goes through the driver: ``setup()`` for the first seed,
``reseed()`` for the others (the same compiled step, that seed's weights and
batches), the reference after each.  For the control seeds it also reads the
control (the reference in the precision below the configuration's, in the
program's place) and the planted half batch.  A serving cell runs a short
window per seed at the cell's load.  One JSON line per reading: every number
the driver can compare, and ``correct`` as the cell's limits decide it.  The
exit code is 1 where a reading of the program is not correct, or a control or
a fault is.

``--nudge 1e-6`` adds the look at the model's own sensitivity: the reference
with its first parameters moved by that share, against the reference.

``--program-float32`` is the witness for a training cell whose bfloat16 runs
read wide by the worst leaf: the same program with float32 parameters and
compute, its matrix products at ``highest``, against the same reference.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class Readings:
    def __init__(self, limits):
        self.limits = limits
        self.unexpected = 0

    def emit(self, seed, what, checked, sound, **more):
        """``checked`` is a driver's [(name, value, limit, where)]; a sound
        reading has to be correct by the cell's limits, any other not."""
        held = {n: v <= self.limits[n] for n, v, _l, _w in checked
                if n in self.limits}
        correct = all(held.values())
        if sound is not None:       # None: a look, held to nothing
            self.unexpected += correct != sound
        row = {"seed": seed, "reading": what, "correct": correct,
               "expected": sound}
        row.update({n: v for n, v, _lim, _w in checked})
        row["where"] = {n: w for n, _v, _lim, w in checked if w}
        row.update(more)
        print(json.dumps(row), flush=True)


def train_readings(drv, seeds, control_seeds, control_cast, out, nudge):
    from benchmark.drivers import train
    every = dict.fromkeys(train.NUMBERS, 0)     # all numbers, for the record
    every.update(drv.ctx.limits)
    for i, seed in enumerate(seeds):
        drv.setup() if i == 0 else drv.reseed(seed)
        prog = drv.program_readings()
        drv.release(keep_step=True)
        ref = drv.reference_readings()
        out.emit(seed, "program", train.checks(prog, ref, every), True,
                 loss_gap=train.loss_gap(prog, ref), loss=prog["loss"],
                 ref_cross_entropy=ref["cross_entropy"])
        if nudge:
            # the reference against itself: no run of the program, not judged
            moved = drv.reference_readings(nudge=nudge)
            out.emit(seed, "reference:nudged_%g" % nudge,
                     train.checks(moved, ref, every), None)
        if seed in control_seeds:
            ctrl = drv.reference_readings(cast=control_cast)
            out.emit(seed, "control:%s" % control_cast.__name__,
                     train.checks(ctrl, ref, every), False,
                     loss_gap=train.loss_gap(ctrl, ref))
            half = drv.reference_readings(alter=train.half_doubled)
            out.emit(seed, "fault:half_batch",
                     train.checks(half, ref, every), False)


def serve_readings(driver_for, seeds, control_seeds, control_cast, seconds,
                   out):
    for seed in seeds:
        drv = driver_for(seed)
        drv.setup()
        facts = drv.window(seconds)
        drv.release()
        out.emit(seed, "program", drv.verify(), True,
                 finished=facts["attempted"])
        if seed in control_seeds:
            out.emit(seed, "control:%s" % control_cast.__name__,
                     drv.verify(cast=control_cast), False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--program-float32", action="store_true")
    ap.add_argument("--nudge", type=float, default=0.0)
    ap.add_argument("--benchmark-json",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    import mxnet_tpu  # noqa: F401
    import jax
    import jax.numpy as jnp
    from benchmark import run
    from benchmark.lib import harness
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    _bench, cell, files, data = run.load_cell(args.benchmark_json,
                                              args.workload)
    if args.program_float32:
        data["cfg"]["training"].update(param_dtype="float32",
                                       compute_dtype="float32")
        jax.config.update("jax_default_matmul_precision", "highest")
    devices = jax.devices()[:cell["chips"]]
    print(json.dumps({"device": str(devices[0].device_kind),
                      "program_float32": args.program_float32}), flush=True)

    def driver_for(seed):
        ctx = run.Context(files=files, seed=seed, devices=devices,
                          spans=harness.Spans(), root=ROOT, cell=cell, **data)
        return files.module("drivers", data["traffic"]["driver"]).Driver(ctx)

    out = Readings(data["limits"])
    below = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}
    if data["traffic"]["driver"] == "train":
        cast = getattr(jnp, below[data["cfg"]["training"]["compute_dtype"]])
        train_readings(driver_for(seeds[0]), seeds, control, cast, out,
                       args.nudge)
    else:
        cast = getattr(jnp, below[data["cfg"]["serving"]["dtype"]])
        serve_readings(driver_for, seeds, control, cast, args.seconds, out)
    print(json.dumps({"unexpected": out.unexpected}), flush=True)
    return 1 if out.unexpected else 0


if __name__ == "__main__":
    sys.exit(main())
