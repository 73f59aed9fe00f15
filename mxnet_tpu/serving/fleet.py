"""Serving fleet: N replica processes + supervisor + router, one object.

This is the serving plane's multi-process jump, mirroring what elastic
training (resilience/elastic.py + tools/launch.py) did for the training
plane, and reusing its conventions as the process-management substrate:

* replicas are plain OS processes (``python -m mxnet_tpu.serving.replica``)
  supervised per-slot: a replica that exits with the elastic launcher's
  RESIZE/restart code (44) is relaunched immediately (a deliberate,
  coordinated restart); any other death (crash, SIGKILL, OOM-kill) is
  relaunched after ``restart_backoff`` — so a crashed replica is
  restarted, canaried by the router, and re-enrolled **without operator
  action**;
* the fleet advertises its capacity in ``fleet-capacity.json`` (the
  ``elastic-capacity.json`` analog from tools/launch.py);
* membership/health ride the PR-5 heartbeat/digest lane over a
  :class:`resilience.watchdog.FileKVClient` under ``<fleet_dir>/kv`` —
  the same HeartbeatLane class training ranks use, different backing
  store (serving replicas are not a jax.distributed gang: rank 0 of a
  gang must never be serving's single point of failure).

Quick start::

    from mxnet_tpu.serving.fleet import ServingFleet
    with ServingFleet(3, artifact="model.mxt") as fleet:
        out = fleet.predict(data=example, tenant="search")
        fleet.swap("model-v2.mxt")        # rolling, canaried, auto-rollback

The router half (membership, quotas, hedging, rolling swap) is
:class:`serving.router.FleetRouter`; this module only owns process
lifecycle and wiring.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

from .. import telemetry
from ..resilience.watchdog import FileKVClient, HeartbeatLane
from .errors import ServingError
from .router import FleetRouter

__all__ = ["ServingFleet", "ReplicaSupervisor", "fleet_lane",
           "events_path", "KV_SUBDIR", "EVENTS_FILE", "CAPACITY_FILE",
           "ROUTER_RANK"]

KV_SUBDIR = "kv"
EVENTS_FILE = "fleet-events.jsonl"
CAPACITY_FILE = "fleet-capacity.json"
# the lane rank the ROUTER publishes its per-tenant SLO digest under —
# far above any replica id, so replica rows and the router row never
# collide in the KV (digest kind "router" vs "serving" disambiguates)
ROUTER_RANK = 1 << 16


def _env_float(name, default):
    try:
        return float(os.environ[name])
    except (KeyError, ValueError):
        return default


def fleet_lane(fleet_dir: str, rank: Optional[int] = None) -> HeartbeatLane:
    """The fleet's coordination-KV heartbeat lane: the PR-5
    :class:`HeartbeatLane` over a file-backed KV under
    ``<fleet_dir>/kv``.  ``rank`` pins the publishing replica id
    (readers leave it None)."""
    return HeartbeatLane(
        client=FileKVClient(os.path.join(os.fspath(fleet_dir), KV_SUBDIR)),
        rank=rank)


def events_path(fleet_dir: str) -> str:
    return os.path.join(os.fspath(fleet_dir), EVENTS_FILE)


def write_capacity(fleet_dir: str, replicas: int):
    """Advertise deliverable replica capacity (tools/launch.py
    ``write_capacity`` analog, same atomic write-then-rename)."""
    path = os.path.join(os.fspath(fleet_dir), CAPACITY_FILE)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"replicas": int(replicas), "time": time.time()}, f)
    os.replace(tmp, path)


class ReplicaSupervisor:
    """Keep one replica slot alive: spawn, monitor, relaunch.

    Exit 44 (the elastic RESIZE/restart convention) relaunches
    immediately; exit 0 after :meth:`stop` ends the slot; anything else
    is a crash — relaunched after ``restart_backoff`` seconds, at most
    ``max_restarts`` times (None = forever, the serving default: a
    serving fleet heals, it does not give up)."""

    def __init__(self, slot: int, fleet_dir: str, argv: List[str],
                 env: Optional[Dict[str, str]] = None,
                 restart_backoff: Optional[float] = None,
                 max_restarts: Optional[int] = None):
        self.slot = int(slot)
        self._fleet_dir = os.fspath(fleet_dir)
        self._argv = list(argv)
        self._env = dict(env or {})
        self._backoff = (restart_backoff if restart_backoff is not None
                         else _env_float(
                             "MXNET_TPU_FLEET_RESTART_BACKOFF", 0.2))
        self._max_restarts = max_restarts
        self.restarts = 0
        self._proc: Optional[subprocess.Popen] = None
        self._stopping = False
        self._lock = threading.Lock()
        self._spawn()
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="mxt-fleet-sup-%d" % slot,
            daemon=True)
        self._monitor.start()

    def _spawn(self):
        env = dict(os.environ)
        env.update(self._env)
        with self._lock:
            self._proc = subprocess.Popen(self._argv, env=env)

    def _monitor_loop(self):
        from .replica import RESTART_EXIT_CODE
        while True:
            proc = self._proc
            code = proc.wait()
            if self._stopping:
                return
            if code == 0:
                return          # clean shutdown op: the slot is done
            deliberate = (code == RESTART_EXIT_CODE)
            telemetry.count("fleet.replica_restarts",
                            slot=str(self.slot),
                            cause="requested" if deliberate else "crash")
            if (self._max_restarts is not None
                    and self.restarts >= self._max_restarts):
                return
            if not deliberate:
                time.sleep(self._backoff)
            if self._stopping:
                return
            self.restarts += 1
            self._spawn()

    @property
    def pid(self) -> Optional[int]:
        with self._lock:
            return self._proc.pid if self._proc is not None else None

    def alive(self) -> bool:
        with self._lock:
            return self._proc is not None and self._proc.poll() is None

    def kill(self, sig=signal.SIGKILL):
        """Hard-kill the CURRENT process (drills).  The monitor loop
        relaunches it — that is the point of the drill."""
        with self._lock:
            if self._proc is not None and self._proc.poll() is None:
                os.kill(self._proc.pid, sig)

    def stop(self, timeout: float = 5.0):
        self._stopping = True
        with self._lock:
            proc = self._proc
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=timeout)
        self._monitor.join(timeout=2.0)


class ServingFleet:
    """N supervised replica processes behind a :class:`FleetRouter`.

    ``artifact`` serves a real exported model; ``synthetic=(B, F,
    latency)`` serves the device-free synthetic program (benches,
    drills).  ``replica_env`` maps slot -> extra env for that replica's
    process (chaos arming in drills: ``{1: {"MXNET_TPU_CHAOS":
    "hedge_lagx100000"}}``).  All ``FleetRouter`` keyword knobs pass
    through ``router_kw``.

    Chips: a chip belongs to one process, and nothing here assigns one —
    replicas that serve from a TPU must each be given their own through
    ``replica_env`` (``{i: {"TPU_VISIBLE_CHIPS": str(i),
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1", "TPU_PROCESS_BOUNDS":
    "1,1,1"}}`` gives slot ``i`` chip ``i`` of a v5e host; PERF.md, PR 21),
    and the process that builds the fleet must not have touched jax."""

    def __init__(self, n_replicas: int, *, artifact=None, synthetic=None,
                 fleet_dir=None, quotas=None, replica_env=None,
                 wait_ready=True, ready_timeout: float = 60.0,
                 restart_backoff=None, **router_kw):
        if (artifact is None) == (synthetic is None):
            raise ValueError("need exactly one of artifact= / synthetic=")
        self.n_replicas = int(n_replicas)
        self.fleet_dir = os.fspath(fleet_dir) if fleet_dir else \
            tempfile.mkdtemp(prefix="mxt-fleet-")
        os.makedirs(self.fleet_dir, exist_ok=True)
        write_capacity(self.fleet_dir, self.n_replicas)
        self._closing = False

        base = [sys.executable, "-m", "mxnet_tpu.serving.replica",
                "--fleet-dir", self.fleet_dir]
        if artifact is not None:
            base += ["--artifact", os.fspath(artifact)]
        else:
            base += ["--synthetic",
                     ",".join(str(x) for x in synthetic)]
        env_common = {"MXNET_TPU_FLEET_DIR": self.fleet_dir,
                      # replicas must import mxnet_tpu from THIS repo
                      "PYTHONPATH": os.pathsep.join(
                          [os.path.dirname(os.path.dirname(
                              os.path.dirname(os.path.abspath(__file__))))]
                          + os.environ.get("PYTHONPATH", "").split(
                              os.pathsep)).rstrip(os.pathsep)}
        # distributed tracing: when this process armed tracing but no
        # sink dir is pinned, every replica's trace sink lands in the
        # fleet dir — one directory for tracewatch to merge
        from ..telemetry import tracing
        if tracing.is_armed():
            env_common.setdefault("MXNET_TPU_TRACE", "1")
            if not os.environ.get("MXNET_TPU_TRACE_DIR"):
                env_common["MXNET_TPU_TRACE_DIR"] = self.fleet_dir
        self.supervisors: Dict[int, ReplicaSupervisor] = {}
        for slot in range(self.n_replicas):
            env = dict(env_common)
            env.update((replica_env or {}).get(slot, {}))
            self.supervisors[slot] = ReplicaSupervisor(
                slot, self.fleet_dir,
                base + ["--replica-id", str(slot)], env=env,
                restart_backoff=restart_backoff)
        self.router = FleetRouter(self.fleet_dir, quotas=quotas,
                                  **router_kw)
        if wait_ready and not self.router.wait_ready(self.n_replicas,
                                                     timeout=ready_timeout):
            state = self.router.replicas()
            self.close()
            raise ServingError(
                "fleet did not reach %d READY replicas within %.0fs: %s"
                % (self.n_replicas, ready_timeout, state))

    # -- client surface ----------------------------------------------------
    def submit(self, inputs=None, **kw):
        return self.router.submit(inputs, **kw)

    def predict(self, inputs=None, **kw):
        return self.router.predict(inputs, **kw)

    def swap(self, source, tag=None):
        return self.router.swap_fleet(source, tag=tag)

    def stats(self) -> dict:
        return self.router.stats()

    # -- drills ------------------------------------------------------------
    def kill_replica(self, slot: int, sig=signal.SIGKILL) -> Optional[int]:
        """SIGKILL one replica's current process (the supervisor will
        relaunch it).  Returns the killed pid."""
        sup = self.supervisors[slot]
        pid = sup.pid
        sup.kill(sig)
        return pid

    # -- lifecycle ---------------------------------------------------------
    def close(self):
        if self._closing:
            return
        self._closing = True
        for sup in self.supervisors.values():
            sup._stopping = True        # no relaunch races during teardown
        for sup in self.supervisors.values():
            sup.stop()
        if getattr(self, "router", None) is not None:
            self.router.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
