"""Distributed request tracing: one fleet-wide trace from router to device.

The spans plane (:mod:`.spans`) answers "what is THIS process doing";
this module answers "what happened to THAT request" — across the
router, two hedged replicas, a kill, and a re-dispatch.  It is a thin
context-propagation layer over the existing span/registry machinery:

* a **trace context** — ``trace_id`` / ``span_id`` / ``parent_id`` plus
  a sampling bit — is minted at the fleet router's ``submit`` (the one
  place every request passes exactly once), rides the wire frame's JSON
  header under the reserved ``"trace"`` key (serving/wire.py), and is
  rebound in the replica server so every serving-side phase of that
  request becomes a child span of the router's dispatch;
* every **dispatch** — first copy, hedge, re-dispatch after an eviction
  — is its own child span tagged with its outcome (``ok``, ``error:*``,
  ``cancelled`` for hedge losers, ``deadline``), so a request's tree
  IS its fleet history;
* each process appends finished spans to a **bounded JSONL trace sink**
  (flight-recorder style: newest spans win, the file self-compacts) in
  the standard forensics dir, and the stdlib-only ``tools/tracewatch.py``
  merges every process's sink into ONE Perfetto trace with flow events
  linking the cross-process parent/child edges.

Nothing here talks to a collector or adds a thread: recording is an
append to a line-buffered local file, reading is offline.  A SIGKILLed
replica's spans survive because they were flushed when they finished —
that is the flight-recorder contract the kill drill tests.

Env knobs (cached at first use; :func:`reset` re-reads — tests):

=====================================  ==================================
``MXNET_TPU_TRACE``                    master switch: ``1`` arms tracing
``MXNET_TPU_TRACE_SAMPLE``             probability a new trace records
                                       spans (default 1.0; unsampled
                                       traces still mint ids so event
                                       logs stay correlatable)
``MXNET_TPU_TRACE_DIR``                sink directory (default: the
                                       watchdog forensics dir, else cwd)
``MXNET_TPU_TRACE_MAX_SPANS``          sink bound per process (20000);
                                       the file compacts to the newest
                                       half when it fills
=====================================  ==================================
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional

from . import registry as _registry

__all__ = ["TraceContext", "arm", "disarm", "is_armed", "sample_rate",
           "new_context", "child_context", "from_wire", "current", "bind",
           "record", "record_served_request", "request_outcome",
           "note_span", "note_compile",
           "compile_summary", "set_process_label", "sink_path",
           "set_sink_dir", "flush", "reset", "mono_to_epoch"]

_ARMED: Optional[bool] = None        # None -> read env on first check
_SAMPLE: Optional[float] = None
_TLS = threading.local()

# one anchor per process: converts the monotonic timestamps the serving
# hot path already records into the shared epoch clock the merged trace
# needs (same-host processes agree on epoch; monotonic clocks do not)
_EPOCH_ANCHOR = time.time() - time.monotonic()

_LABEL = [None]                      # process label in every span record


def is_armed() -> bool:
    """Cheap cached master-switch check (the hot-path gate)."""
    global _ARMED
    if _ARMED is None:
        _ARMED = os.environ.get("MXNET_TPU_TRACE", "") not in (
            "", "0", "false", "off")
    return _ARMED


def arm(sample: Optional[float] = None):
    """Turn tracing on for this process (optionally pinning the sample
    rate — tests; env still wins for child processes)."""
    global _ARMED, _SAMPLE
    _ARMED = True
    if sample is not None:
        _SAMPLE = float(sample)


def disarm():
    global _ARMED
    _ARMED = False


def sample_rate() -> float:
    global _SAMPLE
    if _SAMPLE is None:
        try:
            _SAMPLE = min(1.0, max(
                0.0, float(os.environ["MXNET_TPU_TRACE_SAMPLE"])))
        except (KeyError, ValueError):
            _SAMPLE = 1.0
    return _SAMPLE


def reset():
    """Drop cached env state + the sink (tests)."""
    global _ARMED, _SAMPLE, _SINK
    _ARMED = None
    _SAMPLE = None
    with _SINK_LOCK:
        _SINK = None
    _COMPILES_LOCK_FREE.clear()
    _LABEL[0] = None


def set_process_label(label: str):
    """Name this process in every span it records (``router``,
    ``replica0``, ...).  Defaults to ``pid<pid>``."""
    _LABEL[0] = str(label)


def _label() -> str:
    return _LABEL[0] or ("pid%d" % os.getpid())


def mono_to_epoch(t_mono: float) -> float:
    """A ``time.monotonic()`` timestamp on this process's epoch clock."""
    return t_mono + _EPOCH_ANCHOR


# ---------------------------------------------------------------------------
# trace context
# ---------------------------------------------------------------------------

_ID_LOCK = threading.Lock()
_ID_STATE = [None, 0]        # (prefix hex, counter) — cheap unique ids


def _new_id() -> str:
    """16-hex-char id: a per-process random prefix + a counter — unique
    across processes without per-call entropy reads."""
    with _ID_LOCK:
        if _ID_STATE[0] is None:
            _ID_STATE[0] = os.urandom(5).hex()       # 10 hex chars
        _ID_STATE[1] += 1
        return "%s%06x" % (_ID_STATE[0], _ID_STATE[1] & 0xFFFFFF)


class TraceContext:
    """One request's position in its trace: ``trace_id`` names the whole
    request, ``span_id`` the span this process is inside, ``parent_id``
    that span's parent (None at the root).  ``sampled`` rides along so
    every hop honors the root's recording decision."""

    __slots__ = ("trace_id", "span_id", "parent_id", "sampled")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.sampled = bool(sampled)

    def child(self) -> "TraceContext":
        """A child context: new span under this one, same trace."""
        return TraceContext(self.trace_id, _new_id(), self.span_id,
                            self.sampled)

    def to_wire(self) -> Dict:
        """Compact JSON-able form for the wire frame header."""
        return {"tid": self.trace_id, "sid": self.span_id,
                "smp": 1 if self.sampled else 0}

    def __repr__(self):
        return ("TraceContext(%s/%s<-%s%s)"
                % (self.trace_id, self.span_id, self.parent_id,
                   "" if self.sampled else " unsampled"))


def new_context() -> Optional[TraceContext]:
    """Mint a root context, or None when tracing is disarmed.  The
    sampling decision is made HERE, once per trace: unsampled contexts
    still carry ids (event logs stay correlatable) but record no spans."""
    if not is_armed():
        return None
    rate = sample_rate()
    sampled = rate >= 1.0 or (_ID_STATE[1] * 2654435761 % (1 << 32)
                              < rate * (1 << 32))
    return TraceContext(_new_id(), _new_id(), None, sampled)


def child_context(ctx: Optional[TraceContext]) -> Optional[TraceContext]:
    return None if ctx is None else ctx.child()


def from_wire(d) -> Optional[TraceContext]:
    """Rebind a context that arrived in a wire frame header (the replica
    side of propagation): the sender's span id becomes the PARENT of a
    fresh local span, so this process's spans nest under the dispatch
    that carried them (W3C-traceparent discipline).  Tolerates absent or
    garbage values — a trace is never worth failing a request over."""
    if not isinstance(d, dict) or not d.get("tid") or not d.get("sid"):
        return None
    return TraceContext(str(d["tid"]), _new_id(), str(d["sid"]),
                        sampled=bool(d.get("smp", 1)))


def current() -> Optional[TraceContext]:
    """The context bound to this thread (via :func:`bind`), or None."""
    return getattr(_TLS, "ctx", None)


class bind:
    """Bind ``ctx`` to the current thread for a ``with`` block, so
    :func:`note_span` (fed by every :class:`telemetry.span` exit) knows
    which trace the enclosed work belongs to."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_TLS, "ctx", None)
        _TLS.ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc):
        _TLS.ctx = self._prev
        return False


# ---------------------------------------------------------------------------
# bounded per-process JSONL sink (flight recorder)
# ---------------------------------------------------------------------------

class TraceSink:
    """Append-only JSONL span sink with a hard bound: at ``max_spans``
    lines the file compacts to its newest half (flight-recorder
    semantics — the most recent spans are the ones a post-mortem needs).
    Every append is flushed so a SIGKILL loses at most the span being
    written, never the spans already finished."""

    def __init__(self, path: str, max_spans: Optional[int] = None):
        if max_spans is None:
            try:
                max_spans = int(os.environ["MXNET_TPU_TRACE_MAX_SPANS"])
            except (KeyError, ValueError):
                max_spans = 20000
        self.path = path
        self.max_spans = max(2, int(max_spans))
        self._lock = threading.Lock()
        self._file = None
        self._count = 0

    def append(self, rec: dict):
        line = json.dumps(rec, default=repr)
        with self._lock:
            if self._file is None:
                os.makedirs(os.path.dirname(self.path) or ".",
                            exist_ok=True)
                self._file = open(self.path, "a", buffering=1)
                self._count = 0
                if os.path.getsize(self.path):
                    with open(self.path) as f:
                        self._count = sum(1 for _ in f)
            self._file.write(line + "\n")
            self._count += 1
            if self._count >= self.max_spans:
                self._compact()

    def _compact(self):
        """Keep the newest half, atomically (lock held)."""
        self._file.close()
        try:
            with open(self.path) as f:
                lines = f.readlines()
            keep = lines[len(lines) - self.max_spans // 2:]
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                f.writelines(keep)
            os.replace(tmp, self.path)
            self._count = len(keep)
        finally:
            self._file = open(self.path, "a", buffering=1)

    def close(self):
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


_SINK: Optional[TraceSink] = None
_SINK_LOCK = threading.Lock()
_SINK_DIR = [None]


def set_sink_dir(path: str):
    """Pin the sink directory for this process (wins over the watchdog
    forensics default; explicit ``MXNET_TPU_TRACE_DIR`` still wins over
    both).  No-op once the sink has opened."""
    _SINK_DIR[0] = os.fspath(path)


def _sink_dir() -> str:
    env = os.environ.get("MXNET_TPU_TRACE_DIR")
    if env:
        return env
    if _SINK_DIR[0]:
        return _SINK_DIR[0]
    try:
        from ..resilience import watchdog
        d = watchdog.default_report_dir()
        if d:
            return d
    except Exception:
        pass
    return "."


def _sink() -> TraceSink:
    global _SINK
    with _SINK_LOCK:
        if _SINK is None:
            _SINK = TraceSink(os.path.join(
                _sink_dir(), "trace-%s-%d.jsonl" % (_label(), os.getpid())))
        return _SINK


def sink_path() -> Optional[str]:
    """This process's sink file (None until the first span records)."""
    return _SINK.path if _SINK is not None else None


def flush():
    """No-op placeholder for symmetry — appends are already flushed
    line-by-line (the flight-recorder contract)."""


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

def record(name: str, ctx: Optional[TraceContext], start_s: float,
           dur_s: float, parent_id: Optional[str] = None, cat: str = "trace",
           outcome: str = "ok", **attrs) -> Optional[str]:
    """Record one finished span of ``ctx``'s trace into this process's
    sink.  ``start_s`` is EPOCH seconds (use :func:`mono_to_epoch` for
    monotonic timestamps).  ``parent_id`` overrides the context's parent
    (request-lane reconstruction nests phases under a span this same
    call minted).  Returns the recorded span id, or None when the trace
    is unsampled/absent."""
    if ctx is None or not ctx.sampled or not is_armed():
        return None
    span_id = ctx.span_id if parent_id is None else _new_id()
    rec = {"trace": ctx.trace_id, "span": span_id,
           "parent": parent_id if parent_id is not None else ctx.parent_id,
           "name": name, "cat": cat, "proc": _label(), "pid": os.getpid(),
           "t0": round(start_s, 6), "dur": round(max(0.0, dur_s), 6),
           "outcome": outcome}
    if attrs:
        rec["attrs"] = attrs
    _sink().append(rec)
    if _registry.is_armed():
        _registry.counter("trace.spans").inc(1.0, name=name,
                                             outcome=outcome)
    return span_id


def note_span(name: str, cat: str, start_epoch_s: float, dur_s: float,
              attrs=None):
    """Called by :class:`telemetry.span` on exit when tracing is armed:
    if the current thread is bound to a trace (:func:`bind`), the span
    also lands in the trace sink as a child of the bound context — the
    bridge that lets ordinary in-process spans join a distributed
    trace without knowing about it."""
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None or not ctx.sampled:
        return
    record(name, ctx, start_epoch_s, dur_s, parent_id=ctx.span_id,
           cat=cat, **(attrs or {}))


def request_outcome(req) -> str:
    """Canonical outcome tag for a settled request future: ``ok``,
    ``cancelled`` (hedge loser / router cancel), ``deadline``, or
    ``error:<TypedError>`` — the vocabulary every span in a request's
    tree shares."""
    err = getattr(req, "_error", None)
    if err is None:
        return "ok" if getattr(req, "done", True) else "open"
    kind = type(err).__name__
    if kind == "Cancelled":
        return "cancelled"
    if kind == "DeadlineExceeded":
        return "deadline"
    return "error:" + kind


def record_served_request(req, name: str = "replica/request"):
    """Reconstruct one settled serving request's admission → queue →
    batch-fill → exec → deliver lanes from the timestamps the hot path
    already records (serving/request.py) and record them as a span tree
    under the request's wire-propagated context.  Exactly-once is the
    caller's job (the replica server owns each request's settle point);
    a request with no context is a no-op."""
    ctx = getattr(req, "trace", None)
    if ctx is None or not ctx.sampled or not is_armed():
        return
    end = req.done_at if req.done_at is not None else time.monotonic()
    t0 = req.enqueued_at
    outcome = request_outcome(req)
    attrs = {"seq": req.seq, "rows": req.rows, "priority": req.priority}
    batch_seq = getattr(req, "batch_seq", None)
    if batch_seq is not None:
        attrs["batch"] = batch_seq
    # the request span itself sits AT the wire context (child of the
    # router's dispatch span); its phases nest under it
    root = record(name, ctx, mono_to_epoch(t0), end - t0, cat="serve",
                  outcome=outcome, **attrs)
    if root is None:
        return
    phases = []
    popped = min(req.t_popped if req.t_popped is not None else end, end)
    phases.append(("serve/queue_wait", t0, popped))
    disp = min(req.t_dispatched if req.t_dispatched is not None else popped,
               end)
    if disp > popped:
        phases.append(("serve/batch_fill", popped, disp))
    ex = min(req.t_exec_done if req.t_exec_done is not None else end, end)
    if ex > disp:
        phases.append(("serve/exec", disp, ex))
    if end > ex:
        phases.append(("serve/deliver", ex, end))
    for pname, a, b in phases:
        record(pname, ctx, mono_to_epoch(a), b - a, parent_id=root,
               cat="serve", outcome=outcome)


# ---------------------------------------------------------------------------
# compile accounting (ROADMAP item 5 prep: compile/* span family)
# ---------------------------------------------------------------------------

# every compile event, armed or not: compiles are rare and seconds-long,
# so an always-on list is free — and the PERF_LEDGER compile_seconds
# extra must exist without arming telemetry (same deal as peak_hbm_bytes)
_COMPILES_LOCK_FREE: List[dict] = []


def note_compile(name: str, seconds: float, **attrs):
    """Record one compile event (``compile/*`` span family): feeds the
    ``compile.seconds`` registry histogram when telemetry is armed, an
    always-on in-process log that :func:`compile_summary` folds into
    the gated ``compile_seconds`` bench/ledger metric, and — when
    tracing is armed — a root span in this process's flight-recorder
    sink, so ``tools/postmortem.py --compile`` and tracewatch can prove
    a recovery window compiled nothing (every span carries the compile
    cache's ``result`` tag: hit/miss/standby)."""
    seconds = float(seconds or 0.0)
    _COMPILES_LOCK_FREE.append({"name": name, "seconds": seconds,
                                "time": time.time(), **attrs})
    del _COMPILES_LOCK_FREE[:-256]
    if _registry.is_armed():
        _registry.observe("compile.seconds", seconds, what=name)
    if is_armed():
        try:
            ctx = TraceContext(_new_id(), _new_id(), None, True)
            record("compile/%s" % name, ctx, time.time() - seconds,
                   seconds, cat="compile", **attrs)
        except Exception:
            pass            # a trace is never worth failing a compile over


def compile_summary() -> dict:
    """``{"count", "total_seconds", "by_name": {name: seconds},
    "by_result": {result: count}}`` over every compile this process has
    seen.  ``by_result`` counts the compile-cache outcome tags
    (``hit``/``miss``/``standby``/...; events predating the cache count
    as ``untagged``) — the drills assert warmness from it."""
    events = list(_COMPILES_LOCK_FREE)
    by_name: Dict[str, float] = {}
    by_result: Dict[str, int] = {}
    for e in events:
        by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["seconds"]
        r = str(e.get("result", "untagged"))
        by_result[r] = by_result.get(r, 0) + 1
    return {"count": len(events),
            "total_seconds": round(sum(e["seconds"] for e in events), 6),
            "by_name": {k: round(v, 6) for k, v in sorted(by_name.items())},
            "by_result": dict(sorted(by_result.items()))}
