#!/usr/bin/env python3
"""Local multi-process launcher — the reference tools/launch.py analog.

Reference (tools/launch.py:29-50) delegates to the dmlc tracker, whose
*local* mode forks N worker + N server processes with DMLC_* role env vars
so parameter-server code can be tested on one machine
(tests/nightly/test_all.sh:55).

TPU-native collapse: there are no server processes — the "server" is the
collective itself (every rank enters the same psum over the mesh; see
SURVEY.md §5.8).  So the launcher forks N *worker* ranks, points them at a
jax coordination service (the Postoffice/tracker analog), and the workers
initialise jax.distributed.  Env protocol (read by
mxnet_tpu.parallel.init_distributed):

  DMLC_ROLE=worker            kept for reference-script compatibility
  DMLC_NUM_WORKER=<n>
  DMLC_WORKER_ID=<rank>
  MXNET_TPU_COORDINATOR=<host:port>
  MXNET_TPU_DIST_DEVICE=cpu|tpu   (cpu => gloo collectives, for testing
                                   multi-host logic without a pod)

Restart mode (--max-restarts N): a crashed rank kills the whole gang (a
dead peer leaves the others blocked in a collective forever), then the
launcher relaunches ALL ranks up to N times with a fresh coordinator.
Recovery is checkpoint-restart (SURVEY §5.3 failure model): workers read
MXNET_TPU_RESTART_COUNT and resume from their last checkpoint.

Elastic mode (--elastic --min-workers M, resilience/elastic.py): a lost
rank no longer costs the full gang a restart at the ORIGINAL size.  The
survivors run a membership consensus over the coordination KV, commit a
resize manifest into --elastic-dir, and exit with the RESIZE code
(default 44).  The launcher then relaunches the gang at the manifest's
world size (never below --min-workers) with the next generation number
(MXNET_TPU_ELASTIC_GEN).  It also advertises its deliverable capacity
(elastic-capacity.json — locally always the full -n): once the shrunken
gang has soaked, its coordinator grows back the same way, and the
launcher RELAUNCHES THE LOST RANKS instead of failing the gang.  A
non-resize failure falls back to the --max-restarts full-restart path.

Usage:  python tools/launch.py -n 4 [--dist-device cpu]
            [--max-restarts 2]
            [--elastic --min-workers 3 --elastic-dir DIR] python script.py
"""
import argparse
import os
import socket
import subprocess
import sys
import time


import json

RESIZE_EXIT_CODE = int(os.environ.get("MXNET_TPU_ELASTIC_EXIT_CODE", "44"))
_MANIFEST_FMT = "elastic-manifest-g%04d.json"


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def read_manifest(elastic_dir: str, gen: int):
    """The resize manifest a gang commits before exiting 44 (written by
    mxnet_tpu.resilience.elastic; parsed here stdlib-only so the
    launcher never imports the trainee's package)."""
    try:
        with open(os.path.join(elastic_dir, _MANIFEST_FMT % gen)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def write_capacity(elastic_dir: str, workers: int):
    """Advertise deliverable capacity for the gang's grow-back check.
    Locally the launcher can always re-fork the full -n; a fleet-side
    launcher would publish what the resource manager actually grants."""
    os.makedirs(elastic_dir, exist_ok=True)
    path = os.path.join(elastic_dir, "elastic-capacity.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"workers": int(workers), "time": time.time()}, f)
    os.replace(tmp, path)


def decide_next(codes, elastic_dir: str, gen: int, max_workers: int,
                min_workers: int):
    """Elastic gang verdict: ``("done"|"resize"|"fail", new_world)``.

    A gang that exited all-zero is done.  Any RESIZE exit (44) with a
    committed generation-``gen+1`` manifest is a coordinated resize to
    the manifest's world size (clamped to the launcher's capacity,
    refused below ``min_workers``).  Anything else is a plain failure
    for the --max-restarts fallback."""
    if codes and all(c == 0 for c in codes):
        return "done", None
    if any(c == RESIZE_EXIT_CODE for c in codes):
        manifest = read_manifest(elastic_dir, gen + 1)
        if manifest:
            world = min(int(manifest["world_size"]), int(max_workers))
            if world >= int(min_workers):
                return "resize", world
    return "fail", None


def run_gang(args, attempt: int, world=None, generation=0) -> list:
    """Launch ``world`` ranks once; returns every rank's exit code.

    Non-elastic: the first failure kills the rest (a crashed rank leaves
    peers blocked inside a collective forever otherwise).  Elastic: a
    failure does NOT kill the survivors — they are expected to detect
    the loss, agree on a smaller gang and exit with the RESIZE code; the
    launcher only steps in (kill + reap) after --elastic-timeout."""
    world = world if world is not None else args.num_workers
    coordinator = "127.0.0.1:%d" % free_port()
    elastic = bool(getattr(args, "elastic", False))
    procs = []
    for rank in range(world):
        env = dict(os.environ)
        env.update(dict(e.split("=", 1) for e in args.env))
        env.update({
            "DMLC_ROLE": "worker",
            "DMLC_NUM_WORKER": str(world),
            "DMLC_WORKER_ID": str(rank),
            "MXNET_TPU_COORDINATOR": coordinator,
            "MXNET_TPU_DIST_DEVICE": args.dist_device,
            "MXNET_TPU_RESTART_COUNT": str(attempt),
        })
        if elastic:
            env.update({
                "MXNET_TPU_ELASTIC": "1",
                "MXNET_TPU_ELASTIC_GEN": str(generation),
                "MXNET_TPU_ELASTIC_DIR": args.elastic_dir,
                "MXNET_TPU_ELASTIC_MIN_WORKERS": str(args.min_workers),
            })
        procs.append(subprocess.Popen(args.command, env=env))

    codes = [None] * world      # by rank, for bookkeeping
    order = []                  # completion order: first element = first exit
    deadline = None
    try:
        while any(c is None for c in codes):
            for i, p in enumerate(procs):
                if codes[i] is not None:
                    continue
                r = p.poll()
                if r is None:
                    continue
                codes[i] = r
                order.append(r)
                if r == 0 or r == RESIZE_EXIT_CODE:
                    continue
                if elastic:
                    # a lost rank: give the survivors time to notice,
                    # agree, checkpoint and exit with the resize code
                    if deadline is None:
                        deadline = time.time() + args.elastic_timeout
                        print("[launch] rank %d exited rc=%d; waiting up "
                              "to %.0fs for survivors to resize"
                              % (i, r, args.elastic_timeout),
                              file=sys.stderr)
                else:
                    for q in procs:
                        if q.poll() is None:
                            q.kill()
            if elastic and deadline is None and \
                    any(c == RESIZE_EXIT_CODE for c in codes):
                # coordinated resize under way: bound the stragglers too
                deadline = time.time() + args.elastic_timeout
            if deadline is not None and time.time() > deadline:
                print("[launch] elastic wait expired; reaping the gang",
                      file=sys.stderr)
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                deadline = time.time() + 1e9   # collect what's left
            time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for i, p in enumerate(procs):
            # reap before (re)launching: a killed rank still holds the
            # device / coordinator sockets until it is gone
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
            if codes[i] is None:
                codes[i] = p.poll() if p.poll() is not None else 1
                order.append(codes[i])
    return order


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--dist-device", default="cpu",
                    help="device backend for workers: cpu (gloo "
                         "collectives) is the only one this launcher "
                         "can serve")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE env for workers")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="relaunch the whole gang up to N times after a "
                         "failure (checkpoint-restart elasticity)")
    ap.add_argument("--elastic", action="store_true",
                    help="coordinated-resize mode: survivors of a lost "
                         "rank re-form a smaller gang (exit 44 + resize "
                         "manifest) instead of forcing a full restart, "
                         "and grow back when capacity allows")
    ap.add_argument("--min-workers", type=int, default=1,
                    help="never resize the gang below this many ranks")
    ap.add_argument("--elastic-dir", default=None,
                    help="directory for resize manifests + the capacity "
                         "file (default: $MXNET_TPU_ELASTIC_DIR)")
    ap.add_argument("--elastic-timeout", type=float, default=120.0,
                    help="seconds to wait for survivors to resize after "
                         "a rank is lost before reaping the gang")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    if args.dist_device != "cpu":
        ap.error("--dist-device %s: a chip belongs to one process, and this "
                 "launcher starts %d on one host without giving each its "
                 "own, so every rank would open every chip.  On one host a "
                 "single process drives all of its chips (ShardedTrainer "
                 "over a dp mesh); across hosts the pod runtime starts one "
                 "process per host." % (args.dist_device, args.num_workers))
    if args.max_restarts < 0:
        ap.error("--max-restarts must be >= 0")
    if args.elastic:
        args.elastic_dir = (args.elastic_dir
                            or os.environ.get("MXNET_TPU_ELASTIC_DIR"))
        if not args.elastic_dir:
            ap.error("--elastic needs --elastic-dir (or "
                     "MXNET_TPU_ELASTIC_DIR)")
        if not 1 <= args.min_workers <= args.num_workers:
            ap.error("--min-workers must be in [1, -n]")

    if not args.elastic:
        rc = 0
        for attempt in range(args.max_restarts + 1):
            codes = run_gang(args, attempt)
            rc = next((c for c in codes if c != 0), 0)
            if rc == 0:
                break
            if attempt < args.max_restarts:
                print("[launch] gang failed rc=%d; restart %d/%d"
                      % (rc, attempt + 1, args.max_restarts),
                      file=sys.stderr)
        sys.exit(rc)

    # elastic loop: resize on manifests, full-restart on anything else
    write_capacity(args.elastic_dir, args.num_workers)
    world, gen, restarts_left, attempt = args.num_workers, 0, \
        args.max_restarts, 0
    while True:
        codes = run_gang(args, attempt, world=world, generation=gen)
        verdict, new_world = decide_next(codes, args.elastic_dir, gen,
                                         args.num_workers, args.min_workers)
        if verdict == "done":
            sys.exit(0)
        if verdict == "resize":
            gen += 1
            print("[launch] elastic resize: generation %d, world %d -> %d"
                  % (gen, world, new_world), file=sys.stderr)
            world = new_world
            continue
        rc = next((c for c in codes if c not in (0, RESIZE_EXIT_CODE)), 1)
        if restarts_left <= 0:
            sys.exit(rc)
        restarts_left -= 1
        attempt += 1
        print("[launch] gang failed rc=%d (codes=%s); full restart %d/%d"
              % (rc, codes, attempt, args.max_restarts), file=sys.stderr)


if __name__ == "__main__":
    main()
