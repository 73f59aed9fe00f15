"""Profiler — per-op/step timing with Chrome-trace output.

Reference: src/engine/profiler.{h,cc} (OprExecStat profiler.h:40, Chrome
trace dump profiler.cc:147) + python/mxnet/profiler.py.

TPU-natively the heavy lifting is jax.profiler (XPlane → TensorBoard /
Perfetto).  This module keeps the reference's API (profiler_set_config /
profiler_set_state / dump_profile) and ALSO emits a Chrome-trace JSON of
python-level events so the "open chrome://tracing" UX survives.

The event store is **per-thread**: ``record_event`` appends to a buffer
owned by the calling thread (registered once, under a lock, on that
thread's first event), so the hot dispatch path takes NO lock per event
— the reference engine's per-device ``OprExecStat`` vectors, not one
contended global.  ``dump_profile`` snapshots every registered buffer
without draining it, so events recorded while a dump is in flight land
in the next dump instead of being lost.

The dump is the MERGED timeline: op events (ndarray/executor dispatch)
plus every telemetry span (``mxnet_tpu.telemetry.spans``) — trainer
steps, module fwd/bwd, data iterator, collectives, checkpoints, and the
serving admission→batch→dispatch→deliver pipeline — one file, open it
in Perfetto.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import List, Optional

import jax

_state = {"mode": "symbolic", "filename": "profile.json", "running": False,
          "jax_dir": None}

_REG_LOCK = threading.Lock()
_BUFFERS: List[list] = []           # every thread's event list, strong refs
_TLS = threading.local()


def _buf() -> list:
    b = getattr(_TLS, "buf", None)
    if b is None:
        b = []
        _TLS.buf = b
        with _REG_LOCK:
            _BUFFERS.append(b)
    return b


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """reference: MXSetProfilerConfig (c_api.h)."""
    _state["mode"] = mode
    _state["filename"] = filename


def profiler_set_state(state="stop"):
    """reference: MXSetProfilerState; 'run' | 'stop'."""
    if state == "run" and not _state["running"]:
        with _REG_LOCK:
            for b in _BUFFERS:
                del b[:]
        _state["running"] = True
        jax_dir = os.path.splitext(_state["filename"])[0] + "_xplane"
        try:
            jax.profiler.start_trace(jax_dir)
            _state["jax_dir"] = jax_dir
        except Exception:
            _state["jax_dir"] = None
    elif state == "stop" and _state["running"]:
        _state["running"] = False
        if _state["jax_dir"]:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass


set_config = profiler_set_config
set_state = profiler_set_state


def is_running() -> bool:
    """Fast gate for instrumented dispatch paths (zero-cost when off)."""
    return _state["running"]


def record_event(name: str, start_us: float, dur_us: float, cat="operator",
                 args=None, tid: Optional[int] = None, pid: int = 0):
    """Append one trace event — lock-free for the calling thread (its
    buffer is registered once).  ``args`` become the Chrome-trace event
    args (visible on click in Perfetto); ``tid`` overrides the thread
    lane (virtual lanes for retrospective spans)."""
    if not _state["running"]:
        return
    ev = {"name": name, "cat": cat, "ph": "X", "ts": start_us,
          "dur": dur_us, "pid": pid,
          "tid": threading.get_ident() % 1000 if tid is None else tid}
    if args:
        ev["args"] = dict(args)
    _buf().append(ev)


def record_counter(name: str, values: dict, ts_us: Optional[float] = None,
                   pid: int = 2):
    """Counter-track event (``ph: "C"``) in the merged trace — the
    attribution plane's roofline/MFU headline numbers ride these so
    Perfetto shows them as tracks above the span timeline (pid 2: their
    own process group, clear of real threads and serving lanes)."""
    if not _state["running"]:
        return
    _buf().append({"name": name, "cat": "counter", "ph": "C",
                   "ts": time.perf_counter() * 1e6 if ts_us is None
                   else ts_us,
                   "pid": pid, "tid": 0, "args": dict(values)})


def dump_profile():
    """reference: MXDumpProfile — write the merged Chrome trace JSON.

    Reads every thread's buffer WITHOUT draining it (no event recorded
    during the dump is lost; it simply appears in the next dump), sorts
    by timestamp so Perfetto nests slices correctly."""
    with _REG_LOCK:
        bufs = list(_BUFFERS)
    events = []
    for b in bufs:
        events.extend(list(b))
    events.sort(key=lambda e: (e.get("pid", 0), e.get("tid", 0),
                               e.get("ts", 0.0), -e.get("dur", 0.0)))
    trace = {"traceEvents": events, "displayTimeUnit": "ms"}
    with open(_state["filename"], "w") as f:
        json.dump(trace, f)
    return _state["filename"]


dump = dump_profile


class Scope:
    """Context manager timing a region into the trace."""

    def __init__(self, name, cat="python"):
        self.name = name
        self.cat = cat

    def __enter__(self):
        self._t0 = time.perf_counter() * 1e6
        return self

    def __exit__(self, *a):
        t1 = time.perf_counter() * 1e6
        record_event(self.name, self._t0, t1 - self._t0, self.cat)
