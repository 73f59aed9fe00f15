"""The dist_async parameter server (``python -m mxnet_tpu.kvstore.server``).

One plain OS process holding the authoritative weight table — the
``ps::KVServer`` of the reference's ps-lite deployment
(src/kvstore/kvstore_dist_server.h:113), rebuilt on this repo's own
substrates instead of ZMQ:

* transport is the serving plane's pickle-free socket framing
  (serving/wire.py) — JSON header + raw array bytes, nothing on the wire
  is ever executed;
* discovery/coordination ride a FileKVClient directory
  (``MXNET_TPU_KV_DIR``), the same membership substrate the serving
  fleet uses, because the server and its workers are deliberately NOT a
  jax gang;
* process lifecycle is the serving fleet's
  :class:`~mxnet_tpu.serving.fleet.ReplicaSupervisor` (see
  :func:`launch_server`): SIGKILL → relaunch → state restored from the
  newest checkpoint container (resilience/container.py), workers
  re-resolve the fresh port and retry.

Semantics, drilled by tests/test_kvstore_ps.py + tests/test_ps_drills.py:

* **async updates** (reference kvstore_dist_server.h:503): each worker's
  push is applied the moment it arrives — no cross-worker aggregation,
  no global barrier anywhere in the push/pull path.
* **bounded staleness** (``MXNET_TPU_STALENESS_BOUND``): per key, a
  worker whose own push count runs more than K versions ahead of the
  slowest LIVE pushing worker blocks on pull until the server advances
  (SSP).  K=0 degenerates to lockstep sync-equivalent updates; unset /
  negative = unbounded (the reference's dist_async).  A worker's
  connection dying evicts it from the staleness set — kill -9 on a
  straggler costs its in-flight contribution, never the fleet's
  progress.  Workers that only pull (eval readers) are never counted.
* **duplicate-push idempotence** keyed by (worker, version): each
  worker numbers its pushes per key; a retried push whose version is not
  newer than the last applied one is acked but NOT re-applied, so
  retry/backoff over a server outage can never double-apply a gradient,
  and a push the restored checkpoint predates is re-applied exactly
  once.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import sys
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from . import protocol
from ..serving.wire import WireError, recv_msg, send_msg

__all__ = ["KVServer", "launch_server", "main", "CKPT_PREFIX"]

CKPT_PREFIX = "kvckpt"


def _env_int(name, default):
    try:
        return int(os.environ[name])
    except (KeyError, ValueError):
        return int(default)


def _env_float(name, default):
    try:
        return float(os.environ[name])
    except (KeyError, ValueError):
        return float(default)


def staleness_bound() -> Optional[int]:
    """K from ``MXNET_TPU_STALENESS_BOUND``: None = unbounded (pure
    async), 0 = lockstep, K>0 = SSP."""
    raw = os.environ.get("MXNET_TPU_STALENESS_BOUND", "").strip()
    if not raw:
        return None
    k = int(raw)
    return None if k < 0 else k


class KVServer:
    """The server state machine + socket loop.  Usable in-process for
    tests (``serve_in_thread``) or as the supervised subprocess entry
    (:func:`main`)."""

    def __init__(self, kv_dir: str, world: int = 0,
                 staleness: Optional[int] = None,
                 ckpt_interval: Optional[int] = None,
                 pull_timeout: Optional[float] = None):
        self.dir = os.fspath(kv_dir)
        os.makedirs(self.dir, exist_ok=True)
        self.world = int(world)
        self.staleness = staleness if staleness is not None \
            else staleness_bound()
        self.ckpt_interval = ckpt_interval if ckpt_interval is not None \
            else _env_int("MXNET_TPU_KV_CKPT_INTERVAL", 100)
        self.pull_timeout = pull_timeout if pull_timeout is not None \
            else _env_float("MXNET_TPU_KV_PULL_TIMEOUT", 30.0)
        self.epoch = 0
        # key -> NDArray (the authoritative dense table)
        self._values: Dict[str, object] = {}
        self._versions: Dict[str, int] = {}        # key -> applies, mod 2**32
        # (worker, key) -> last APPLIED push version == that worker's
        # push count on that key; doubles as the dedup table and the
        # staleness clock set
        self._applied: Dict[Tuple[int, str], int] = {}
        self._alive: Dict[int, int] = {}           # worker -> conn refcount
        self._ever: set = set()                    # workers seen registering
        self._barrier_arrived: Dict[int, set] = {}
        self._barrier_done: set = set()
        self._updater = None
        self._opt_config: Optional[dict] = None
        self._applies_since_ckpt = 0
        self._ckpt_seq = 0
        self._stats = {"pushes": 0, "pulls": 0, "staleness_waits": 0,
                       "duplicate_pushes": 0, "evictions": 0}
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._sock: Optional[socket.socket] = None
        self._stop = threading.Event()
        self._restore()

    # -- state persistence -------------------------------------------------

    def _ckpt_paths(self):
        try:
            names = sorted(n for n in os.listdir(self.dir)
                           if n.startswith(CKPT_PREFIX + "-")
                           and n.endswith(".mxt"))
        except OSError:
            names = []
        return [os.path.join(self.dir, n) for n in names]

    def _restore(self):
        from ..resilience.container import CorruptContainer, read_container
        for path in reversed(self._ckpt_paths()):
            try:
                arrays, meta, _ = read_container(path)
            except CorruptContainer as e:
                protocol.log_event(self.dir, "restore_skip",
                                   path=os.path.basename(path), error=str(e))
                continue
            self._load_state(arrays, meta)
            self._ckpt_seq = int(meta.get("ckpt_seq", 0))
            protocol.log_event(
                self.dir, "restore", path=os.path.basename(path),
                keys=len(self._values), ckpt_seq=self._ckpt_seq)
            return
        protocol.log_event(self.dir, "restore", path=None, keys=0)

    def _load_state(self, arrays, meta):
        from ..ndarray.ndarray import array as nd_array
        self._values = {}
        for name, arr in arrays.items():
            if name.startswith("value/"):
                self._values[name[len("value/"):]] = nd_array(arr)
        self._versions = {k: int(v)
                          for k, v in meta.get("versions", {}).items()}
        self._applied = {(int(w), str(k)): int(v)
                         for w, k, v in meta.get("applied", [])}
        if meta.get("opt"):
            self._build_updater(meta["opt"])
            layout = meta.get("state_layout", {})
            for key, shape in layout.items():
                self._updater.states[self._ukey(key)] = \
                    self._unflatten_state(key, shape, arrays)
                self._updater.states_synced[self._ukey(key)] = True
            counts = meta.get("update_counts", {})
            self._updater.optimizer._index_update_count = {
                self._ukey(k): int(v) for k, v in counts.items()}
            if counts:
                self._updater.optimizer.num_update = max(
                    int(v) for v in counts.values())

    def _flatten_state(self, key, st, arrays, layout):
        if st is None:
            layout[key] = "none"
        elif isinstance(st, (tuple, list)):
            shape = []
            for i, s in enumerate(st):
                if s is None:
                    shape.append("none")
                else:
                    shape.append("arr")
                    arrays["state/%s/%d" % (key, i)] = s.asnumpy()
            layout[key] = shape
        else:
            layout[key] = "arr"
            arrays["state/%s/0" % key] = st.asnumpy()

    def _unflatten_state(self, key, shape, arrays):
        from ..ndarray.ndarray import array as nd_array
        if shape == "none":
            return None
        if shape == "arr":
            return nd_array(arrays["state/%s/0" % key])
        return tuple(None if s == "none"
                     else nd_array(arrays["state/%s/%d" % (key, i)])
                     for i, s in enumerate(shape))

    def checkpoint(self) -> str:
        """Atomic container snapshot of values + optimizer slots + the
        dedup/staleness tables; keeps the newest two on disk."""
        from ..resilience.container import write_container
        with self._lock:
            arrays = {"value/%s" % k: v.asnumpy()
                      for k, v in self._values.items()}
            layout: Dict[str, object] = {}
            if self._updater is not None:
                for key, st in self._updater.states.items():
                    self._flatten_state(str(key), st, arrays, layout)
            counts = {}
            if self._updater is not None:
                counts = {str(k): int(v) for k, v in
                          self._updater.optimizer._index_update_count
                          .items()}
            self._ckpt_seq += 1
            meta = {"versions": dict(self._versions),
                    "applied": [[w, k, v] for (w, k), v in
                                self._applied.items()],
                    "opt": self._opt_config, "state_layout": layout,
                    "update_counts": counts, "epoch": self.epoch,
                    "ckpt_seq": self._ckpt_seq}
            self._applies_since_ckpt = 0
        path = os.path.join(self.dir, "%s-%010d.mxt"
                            % (CKPT_PREFIX, self._ckpt_seq))
        write_container(path, arrays=arrays, meta=meta)
        for old in self._ckpt_paths()[:-2]:
            try:
                os.unlink(old)
            except OSError:
                pass
        protocol.log_event(self.dir, "checkpoint",
                           path=os.path.basename(path), seq=self._ckpt_seq)
        return path

    # -- update machinery --------------------------------------------------

    @staticmethod
    def _ukey(k):
        try:
            return int(k)
        except ValueError:
            return k

    def _build_updater(self, config):
        from ..optimizer import Optimizer, Updater
        self._opt_config = dict(config)
        opt = Optimizer.create_optimizer(config["name"],
                                         **config.get("params", {}))
        self._updater = Updater(opt)

    def _apply(self, key, grad_nd):
        stored = self._values[key]
        if self._updater is not None:
            self._updater(self._ukey(key), grad_nd, stored)
        else:
            # no server optimizer: merged value REPLACES the stored one,
            # the same update_on_kvstore=False contract KVStore._push keeps
            self._values[key] = grad_nd

    # -- staleness ---------------------------------------------------------

    def _stale_lag(self, worker, key):
        """How far ``worker``'s push count on ``key`` runs ahead of the
        slowest LIVE worker that has pushed that key (0 when nobody else
        pushes — a pull-only reader neither blocks nor holds back)."""
        mine = self._applied.get((worker, key), 0)
        lags = [protocol.clock_lag(mine, v)
                for (w, k), v in self._applied.items()
                if k == key and w != worker and self._alive.get(w, 0) > 0]
        return max(lags) if lags else 0

    def _wait_fresh(self, worker, key):
        """Block the pulling worker while it is more than K versions
        ahead (SSP gate); returns ms waited.  Unbounded lane: no gate."""
        k = self.staleness
        if k is None:
            return 0.0
        start = None
        deadline = time.monotonic() + self.pull_timeout
        while self._stale_lag(worker, key) > k and not self._stop.is_set():
            if start is None:
                start = time.monotonic()
                self._stats["staleness_waits"] += 1
                protocol.log_event(self.dir, "staleness_wait",
                                   worker=worker, key=key,
                                   lag=self._stale_lag(worker, key), bound=k)
                from .. import telemetry
                telemetry.count("kvstore.staleness_waits", key=str(key))
            if not self._cond.wait(timeout=min(
                    0.5, max(0.01, deadline - time.monotonic()))):
                if time.monotonic() >= deadline:
                    raise _RequestError(
                        "staleness timeout: worker %d is %d versions ahead "
                        "on key %r (bound %d) and the lane did not advance "
                        "within %.0fs" % (worker,
                                          self._stale_lag(worker, key),
                                          key, k, self.pull_timeout))
        return 0.0 if start is None else (time.monotonic() - start) * 1e3

    # -- request handlers --------------------------------------------------

    def _handle(self, header, arrays, worker_box):
        op = header.get("op")
        fn = getattr(self, "_op_" + str(op), None)
        if fn is None:
            raise _RequestError("unknown kvstore op %r" % op)
        return fn(header, arrays, worker_box)

    def _op_register(self, header, arrays, worker_box):
        worker = int(header["worker"])
        with self._lock:
            worker_box.append(worker)
            self._alive[worker] = self._alive.get(worker, 0) + 1
            self._ever.add(worker)
            applied = {k: v for (w, k), v in self._applied.items()
                       if w == worker}
            self._cond.notify_all()
        protocol.log_event(self.dir, "register", worker=worker)
        return {"ok": True, "epoch": self.epoch,
                "staleness_bound": self.staleness, "applied": applied}, {}

    def _op_init(self, header, arrays, worker_box):
        from ..ndarray.ndarray import array as nd_array
        key = str(header["key"])
        with self._lock:
            if key not in self._values:
                self._values[key] = nd_array(arrays["value"])
                self._versions[key] = 0
        return {"ok": True, "version": self._versions[key]}, {}

    def _op_push(self, header, arrays, worker_box):
        key = str(header["key"])
        worker = int(header["worker"])
        version = int(header["version"])
        nbytes = sum(int(a.nbytes) for a in arrays.values())
        with self._lock:
            if key not in self._values:
                raise _RequestError("push to uninitialised key %r" % key)
            last = self._applied.get((worker, key))
            if last is not None and \
                    protocol.clock_lag(version, last) <= 0:
                # retried push the server already applied (possibly
                # before a crash the checkpoint survived): ack, don't
                # re-apply — the no-duplicate half of exactly-once
                self._stats["duplicate_pushes"] += 1
                protocol.log_event(self.dir, "push", worker=worker,
                                   key=key, version=version,
                                   applied=False, bytes=nbytes)
                return {"ok": True, "applied": False,
                        "version": self._versions[key]}, {}
            grad_nd = self._wire_grad(header, arrays, key)
            self._apply(key, grad_nd)
            self._applied[(worker, key)] = version
            self._versions[key] = protocol.next_version(
                self._versions.get(key, 0))
            self._stats["pushes"] += 1
            self._applies_since_ckpt += 1
            want_ckpt = (self.ckpt_interval > 0 and
                         self._applies_since_ckpt >= self.ckpt_interval)
            self._cond.notify_all()
        protocol.log_event(self.dir, "push", worker=worker, key=key,
                           version=version, applied=True, bytes=nbytes,
                           sparse=bool(header.get("sparse")))
        from .. import telemetry
        telemetry.count("kvstore.pushes", key=key)
        if want_ckpt:
            self.checkpoint()
        return {"ok": True, "applied": True,
                "version": self._versions[key]}, {}

    def _wire_grad(self, header, arrays, key):
        from ..ndarray.ndarray import array as nd_array
        if not header.get("sparse"):
            return nd_array(arrays["grad"])
        from ..ndarray.sparse import RowSparseNDArray
        import jax.numpy as jnp
        stored = self._values[key]
        return RowSparseNDArray(jnp.asarray(arrays["data"]),
                                jnp.asarray(arrays["indices"]),
                                tuple(stored.shape))

    def _op_pull(self, header, arrays, worker_box):
        key = str(header["key"])
        worker = int(header["worker"])
        with self._lock:
            if key not in self._values:
                raise _RequestError("pull of uninitialised key %r" % key)
            waited = self._wait_fresh(worker, key)
            value = self._values[key].asnumpy()
            version = self._versions[key]
            self._stats["pulls"] += 1
        protocol.log_event(self.dir, "pull", worker=worker, key=key,
                           version=version, waited_ms=round(waited, 3))
        return {"ok": True, "version": version,
                "waited_ms": waited}, {"value": value}

    def _op_pull_rows(self, header, arrays, worker_box):
        """PullRowSparse: only the requested rows cross the wire
        (reference PullRowSparseImpl, kvstore_dist.h:267)."""
        import jax.numpy as jnp
        key = str(header["key"])
        worker = int(header["worker"])
        ids = np.unique(arrays["ids"].astype(np.int64))
        with self._lock:
            if key not in self._values:
                raise _RequestError("pull_rows of uninitialised key %r" % key)
            waited = self._wait_fresh(worker, key)
            stored = self._values[key]
            rows = np.asarray(jnp.take(
                stored._handle, jnp.asarray(ids, jnp.int32), axis=0))
            version = self._versions[key]
            self._stats["pulls"] += 1
        protocol.log_event(self.dir, "pull_rows", worker=worker, key=key,
                           version=version, rows=int(ids.size),
                           waited_ms=round(waited, 3))
        return {"ok": True, "version": version, "waited_ms": waited,
                "shape": list(stored.shape)}, \
            {"data": rows, "indices": ids}

    def _op_set_optimizer(self, header, arrays, worker_box):
        """Pickle-free set_optimizer: the reference ships a pickled
        Optimizer to servers (kvstore.py:435); here only a JSON config
        ``{"name", "params"}`` travels and the server instantiates from
        the registry — nothing on the wire is ever executed."""
        with self._lock:
            if self._updater is None:
                self._build_updater({"name": str(header["name"]),
                                     "params": dict(header.get("params")
                                                    or {})})
        return {"ok": True}, {}

    def _op_barrier(self, header, arrays, worker_box):
        """Coordination barrier over LIVE registered workers (init/eval
        sync points — the async push/pull path never calls it).  A worker
        dying mid-barrier releases the others; the barrier requires every
        configured worker to have registered at least once."""
        worker = int(header["worker"])
        seq = int(header["seq"])
        deadline = time.monotonic() + self.pull_timeout
        with self._lock:
            if seq in self._barrier_done:
                return {"ok": True, "seq": seq}, {}
            arrived = self._barrier_arrived.setdefault(seq, set())
            arrived.add(worker)
            self._cond.notify_all()
            while seq not in self._barrier_done:
                alive = {w for w, c in self._alive.items() if c > 0}
                if (len(self._ever) >= max(self.world, 1)
                        and arrived >= alive):
                    self._barrier_done.add(seq)
                    self._barrier_arrived.pop(seq, None)
                    if len(self._barrier_done) > 64:
                        for s in sorted(self._barrier_done)[:-64]:
                            self._barrier_done.discard(s)
                    self._cond.notify_all()
                    break
                if not self._cond.wait(timeout=min(
                        0.5, max(0.01, deadline - time.monotonic()))):
                    if time.monotonic() >= deadline:
                        raise _RequestError(
                            "barrier %d timed out: arrived=%s alive=%s"
                            % (seq, sorted(arrived), sorted(alive)))
        protocol.log_event(self.dir, "barrier", worker=worker, seq=seq)
        return {"ok": True, "seq": seq}, {}

    def _op_stats(self, header, arrays, worker_box):
        with self._lock:
            return {"ok": True, "epoch": self.epoch,
                    "staleness_bound": self.staleness,
                    "versions": dict(self._versions),
                    "applied": [[w, k, v] for (w, k), v in
                                sorted(self._applied.items())],
                    "alive": sorted(w for w, c in self._alive.items()
                                    if c > 0),
                    "keys": sorted(self._values), **self._stats}, {}

    def _op_checkpoint(self, header, arrays, worker_box):
        return {"ok": True, "path": self.checkpoint()}, {}

    def _op_ping(self, header, arrays, worker_box):
        return {"ok": True, "epoch": self.epoch}, {}

    def _op_shutdown(self, header, arrays, worker_box):
        self._stop.set()
        return {"ok": True}, {}

    # -- socket plumbing ---------------------------------------------------

    def bind(self, port: int = 0) -> int:
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", int(port)))
        self._sock.listen(64)
        self._sock.settimeout(0.2)
        self.port = self._sock.getsockname()[1]
        self.epoch = protocol.publish_endpoint(self.dir, "127.0.0.1",
                                               self.port)
        protocol.log_event(self.dir, "listen", port=self.port,
                           epoch=self.epoch, world=self.world,
                           staleness_bound=self.staleness)
        return self.port

    def serve(self):
        assert self._sock is not None, "bind() first"
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            t = threading.Thread(target=self._conn_loop, args=(conn,),
                                 daemon=True)
            t.start()
        try:
            self._sock.close()
        except OSError:
            pass

    def serve_in_thread(self, port: int = 0) -> int:
        """Tests: bind + run the accept loop on a daemon thread."""
        p = self.bind(port)
        threading.Thread(target=self.serve, daemon=True,
                         name="mxt-kvserver").start()
        return p

    def stop(self):
        self._stop.set()
        with self._lock:
            self._cond.notify_all()

    def _conn_loop(self, conn: socket.socket):
        worker_box: list = []      # filled by the register op
        try:
            while not self._stop.is_set():
                try:
                    header, arrays = recv_msg(conn)
                except (WireError, ConnectionError, OSError):
                    break
                try:
                    reply, out_arrays = self._handle(header, arrays,
                                                     worker_box)
                except _RequestError as e:
                    reply, out_arrays = {"ok": False, "error": str(e)}, {}
                try:
                    send_msg(conn, reply, out_arrays)
                except (WireError, ConnectionError, OSError):
                    break
        finally:
            try:
                conn.close()
            except OSError:
                pass
            self._deregister(worker_box)

    def _deregister(self, worker_box):
        if not worker_box:
            return
        worker = worker_box[0]
        with self._lock:
            n = self._alive.get(worker, 0) - 1
            self._alive[worker] = max(0, n)
            evicted = self._alive[worker] == 0
            # connection death == eviction from the staleness/barrier
            # sets: a SIGKILLed straggler stops gating everyone else
            self._cond.notify_all()
        if evicted:
            self._stats["evictions"] += 1
            protocol.log_event(self.dir, "evict", worker=worker)


class _RequestError(Exception):
    """Per-request failure sent back in-band; the connection survives."""


def launch_server(kv_dir: str, world: int,
                  env: Optional[Dict[str, str]] = None,
                  restart_backoff: Optional[float] = None):
    """Spawn the server as a SUPERVISED subprocess — the serving plane's
    :class:`ReplicaSupervisor` relaunch machinery (SIGKILL → relaunch
    after backoff, exit 44 → immediate relaunch); returns the
    supervisor.  Drills ``sup.kill()`` it and assert recovery."""
    from ..serving.fleet import ReplicaSupervisor
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    # the server is host code: pinned to the CPU, or importing the package
    # would make it open the chip its workers train on
    base_env = {"MXNET_TPU_KV_DIR": os.fspath(kv_dir),
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.pathsep.join(
                    [repo_root] + os.environ.get("PYTHONPATH", "").split(
                        os.pathsep)).rstrip(os.pathsep)}
    base_env.update(env or {})
    argv = [sys.executable, "-m", "mxnet_tpu.kvstore.server",
            "--kv-dir", os.fspath(kv_dir), "--world", str(int(world))]
    return ReplicaSupervisor(0, os.fspath(kv_dir), argv, env=base_env,
                             restart_backoff=restart_backoff)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="mxnet_tpu dist_async parameter server")
    ap.add_argument("--kv-dir", required=True,
                    help="coordination directory (MXNET_TPU_KV_DIR)")
    ap.add_argument("--world", type=int, default=0,
                    help="configured worker count (barrier quorum)")
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args(argv)
    server = KVServer(args.kv_dir, world=args.world)

    def _term(signum, frame):
        # supervised stop: final checkpoint, clean exit 0 ends the slot
        try:
            server.checkpoint()
        except Exception:
            pass
        os._exit(0)

    signal.signal(signal.SIGTERM, _term)
    server.bind(args.port)
    protocol.log_event(args.kv_dir, "start", epoch=server.epoch)
    server.serve()
    try:
        server.checkpoint()
    except Exception:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
