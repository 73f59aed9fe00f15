"""Structured spans: thread-aware nested timing regions.

``span("train/step", step=n)`` is first of all a
``jax.profiler.TraceAnnotation`` of the same name and attrs, entered for
the span's lifetime whether or not anything here is armed: any
``jax.profiler`` trace of the process therefore holds the program's spans
on the host plane and the device's operations in ONE file, on clocks the
profiler lines up to about a millisecond
(``benchmark/lib/program_trace.py`` reads both, ``step_pipeline.py``
checks the clocks).  While no trace is being
taken the annotation is one flag check in C++ and encodes nothing.

Beyond that it times the region and, depending on what is armed, feeds
further consumers from the ONE measurement:

* **trace** — while the profiler runs (``profiler.set_state('run')``)
  every completed span becomes a Chrome-trace ``X`` event in the
  profiler's per-thread buffers, so ``profiler.dump_profile()`` emits a
  SINGLE merged timeline: op events (ndarray/executor dispatch), span
  regions (trainer step, module fwd/bwd, data iterator, checkpoints,
  collectives, serving pipeline), all nested per thread.  This is the
  reference's ``OprExecStat`` chrome dump grown into a whole-system
  trace (open in Perfetto / chrome://tracing).
* **metrics** — when telemetry is armed and the span names a ``metric``,
  its duration is observed into that registry histogram
  (``train.step_seconds`` powers the cross-rank digest).

Open spans are tracked per thread in a process-global table, so a
watchdog post-mortem can report what every thread was *inside* when it
hung — not just its stack.

Cost when nothing is armed: the idle annotation plus one module-bool
check on enter and one on exit; no clock read, no lock (``timed=True``
forces the two clock reads for callers that need ``.duration``
regardless, e.g. the serving EWMA).  A bare ``jax.profiler`` trace arms
nothing here: ``spans_active()`` stays False under it, so a span that
changes what it measures when armed (``train/device_wait`` blocks on the
device) does not start doing so because somebody is looking.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax.profiler

from . import registry as _registry

__all__ = ["span", "spans_active", "open_spans", "record_span"]

_OPEN_LOCK = threading.Lock()
_OPEN: Dict[int, tuple] = {}        # tid -> (thread_name, stack list)
_TLS = threading.local()


def _stack() -> List[dict]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = []
        _TLS.stack = st
        with _OPEN_LOCK:
            _OPEN[threading.get_ident()] = (
                threading.current_thread().name, st)
    return st


def _plain(attrs: dict) -> dict:
    """Attrs as the profiler takes them: TraceMe encodes ``str``, ``int``
    and ``float`` values; anything else goes in as its ``repr``."""
    for v in attrs.values():
        if not isinstance(v, (str, int, float)):
            return {k: v if isinstance(v, (str, int, float)) else repr(v)
                    for k, v in attrs.items()}
    return attrs


def spans_active() -> bool:
    """True when spans record anywhere (telemetry armed, tracing armed,
    OR profiler running) — the single gate the hot path checks."""
    if _registry.is_armed():
        return True
    from . import tracing as _tracing
    if _tracing.is_armed():
        return True
    from .. import profiler
    return profiler.is_running()


class span:
    """Context manager timing one nested region (see module docstring).

    ``metric``: registry histogram name to observe the duration into.
    ``timed``: measure ``.duration`` even when nothing is armed (two
    clock reads) — for callers that feed the measurement into their own
    control loops (serving exec EWMA).
    """

    __slots__ = ("name", "cat", "metric", "attrs", "timed", "active",
                 "duration", "_t0", "_entry", "_ann")

    def __init__(self, name: str, cat: str = "span",
                 metric: Optional[str] = None, timed: bool = False,
                 **attrs):
        self.name = name
        self.cat = cat
        self.metric = metric
        self.attrs = attrs
        self.timed = timed
        self.active = False
        self.duration = None
        self._t0 = None
        self._entry = None
        self._ann = None

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name,
                                                 **_plain(self.attrs))
        self._ann.__enter__()
        self.active = spans_active()
        if self.active:
            self._entry = {"name": self.name, "cat": self.cat,
                           "attrs": self.attrs, "start": time.time()}
            _stack().append(self._entry)
            self._t0 = time.perf_counter()
        elif self.timed:
            self._t0 = time.perf_counter()
        return self

    def annotate(self, **attrs):
        """Attach attrs known only once the work is done (how many were
        admitted, retired); every consumer sees them with the rest."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**_plain(attrs))

    def __exit__(self, *exc):
        if self._t0 is not None:
            self.duration = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if not self.active:
            return False
        st = _stack()
        if st and st[-1] is self._entry:
            st.pop()
        else:                       # exited out of order: drop by identity
            try:
                st.remove(self._entry)
            except ValueError:
                pass
        from .. import profiler
        if profiler.is_running():
            profiler.record_event(self.name, self._t0 * 1e6,
                                  self.duration * 1e6, cat=self.cat,
                                  args=self.attrs or None)
        if self.metric is not None and _registry.is_armed():
            _registry.observe(self.metric, self.duration)
        from . import tracing as _tracing
        if _tracing.is_armed():
            # a thread bound to a trace context (tracing.bind) donates
            # its ordinary spans to the distributed trace too
            _tracing.note_span(self.name, self.cat, self._entry["start"],
                               self.duration, self.attrs)
        return False


def record_span(name: str, start_s: float, dur_s: float, cat: str = "span",
                tid: Optional[int] = None, pid: int = 0, **attrs):
    """Record a RETROSPECTIVE span (explicit start + duration, seconds)
    into the merged trace — for pipelines that reconstruct a request's
    phases from timestamps after delivery (serving).  ``tid``/``pid``
    place the event on a virtual lane (e.g. one per in-flight request
    slot, in its own process group so real thread ids never collide)."""
    from .. import profiler
    if not profiler.is_running():
        return
    profiler.record_event(name, start_s * 1e6, max(0.0, dur_s) * 1e6,
                          cat=cat, tid=tid, pid=pid, args=attrs or None)


def open_spans() -> Dict[str, List[dict]]:
    """``{"<thread> (tid=..)": [outermost..innermost open span]}`` —
    embedded in watchdog post-mortems so a hang report shows what each
    thread was DOING, not just where it stood."""
    with _OPEN_LOCK:
        items = list(_OPEN.items())
    now = time.time()
    out = {}
    for tid, (tname, st) in items:
        frames = [{"name": e["name"], "cat": e["cat"],
                   "attrs": {k: repr(v) for k, v in e["attrs"].items()},
                   "age_sec": round(now - e["start"], 3)}
                  for e in list(st)]
        if frames:
            out["%s (tid=%d)" % (tname, tid)] = frames
    return out
