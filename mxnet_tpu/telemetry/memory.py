"""Memory observability plane: live HBM accounting + OOM forensics.

PRs 5-6 built the TIME axis of observability (spans, step histograms,
roofline/MFU attribution); this module is the SPACE axis.  Three pieces:

* **Live accounting** — every subsystem that materializes device state
  (ShardedTrainer, Module, the optimizer Updater, data iterators,
  CheckpointManager, ServedProgram) calls :func:`tag` on its buffers, so
  ``jax.live_arrays()`` can be bucketed into the tag taxonomy
  (``params`` / ``optimizer`` / ``activations`` / ``batch`` / ``served``
  / ``untagged``).  A sampler folds the buckets into registry gauges
  (``mem.live_bytes{tag=...}``, ``mem.peak_live_bytes``, per-device
  ``mem.device_bytes_in_use`` where the backend reports
  ``memory_stats()``), a bounded in-process timeline (the post-mortem
  window), and — while the profiler runs — a Perfetto **counter track**
  (``memory/live_bytes``) in the merged trace, next to the PR-6
  roofline counters.

* **Per-program attribution** — :func:`note_program` records each
  compiled program's ``memory_analysis()`` breakdown (argument / output
  / temp / alias bytes), fed by the ``MXNET_TPU_ATTRIBUTION`` hooks in
  :mod:`.perf` and by ``build_step_auto_layout``; the attribution report
  reconciles it against the :mod:`~mxnet_tpu.analysis.costmodel`
  entry-signature prediction and the measured live/peak gauges.

* **OOM forensics** — :func:`oom_guard` wraps the dispatch points the
  PR-2 watchdog already arms.  A ``RESOURCE_EXHAUSTED`` escaping the
  region writes ``oom-postmortem-r<rank>-<pid>-<n>.json`` into the
  standard forensics dir (checkpoint/watchdog dir): top-k live buffers
  by size with tags (opt-in creation backtraces), the last-N-seconds
  memory timeline, the compiled breakdown of the program that tripped,
  and an actionable hint (remat / microbatch / ZeRO / donation — the
  GC202/GC501 fix menu).  A :class:`LeakWatchdog` flags monotonic
  live-bytes growth across steps/requests.

Cost model, in the registry's terms: every hook checks one cached gate
(:func:`enabled` — ``MXNET_TPU_MEMWATCH`` explicitly, else armed iff
telemetry is armed) and returns immediately when disarmed — no lock, no
allocation, no ``live_arrays`` walk.  ``oom_guard`` is a bare
try/except on the hot path; it only does work while the process is
already dying of an OOM.

Env knobs (read at first use; :func:`reset` re-reads — tests):

=====================================  ==================================
``MXNET_TPU_MEMWATCH``                 ``1``/``0`` force the gate; unset:
                                       follows the telemetry master switch
``MXNET_TPU_MEMWATCH_INTERVAL``        sampler thread seconds (default 1)
``MXNET_TPU_MEMWATCH_TOPK``            buffers in the OOM table (default 15)
``MXNET_TPU_MEMWATCH_BACKTRACES``      ``1``: record a creation backtrace
                                       per tagged buffer (costly; off)
``MXNET_TPU_MEMWATCH_LEAK_MB``         leak-watchdog growth threshold over
                                       its window (default 64)
``MXNET_TPU_DEVICE_HBM_GB``            per-device capacity override when
                                       the backend reports no
                                       ``memory_stats()`` (CPU dev rigs);
                                       also feeds graphcheck GC501
=====================================  ==================================
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
import traceback
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

from . import registry as _registry

__all__ = ["enabled", "tag", "release", "live_buffers", "top_buffers",
           "live_bytes_by_tag", "tagged_bytes", "device_memory_stats",
           "device_capacity_bytes", "sample_now", "note_step",
           "maybe_start_sampler", "stop_sampler", "memory_window",
           "peak_live_bytes", "measured_snapshot", "note_program",
           "program_memory", "LeakWatchdog", "leak_report", "is_oom",
           "oom_guard", "write_oom_postmortem", "reset", "TAGS"]

TAGS = ("params", "optimizer", "activations", "batch", "served",
        "checkpoint", "embedding", "kv_cache", "untagged")

_UNSET = object()
_ENV_GATE = _UNSET          # None -> defer to telemetry arm state

_TAG_LOCK = threading.Lock()
_TAGGED: Dict[int, tuple] = {}      # id(arr) -> (weakref, tag, label, t, bt)

_TIMELINE: deque = deque(maxlen=512)    # (t, total_bytes, by_tag dict)
_PEAK = [0.0]
_LAST_SAMPLE = [0.0]
_SAMPLER: Optional[threading.Thread] = None
_SAMPLER_STOP = threading.Event()

_PROG_LOCK = threading.Lock()
_PROGRAMS: Dict[str, dict] = {}     # name -> memory_analysis breakdown
_LAST_PROGRAM = [None]              # most recently noted program name

_OOM_SEQ = [0]
_POSTMORTEM_PREFIX = "oom-postmortem"


def _env_float(name, default):
    try:
        return float(os.environ[name])
    except (KeyError, ValueError):
        return float(default)


def enabled() -> bool:
    """The memory-plane gate: ``MXNET_TPU_MEMWATCH`` wins when set;
    otherwise the plane arms exactly when telemetry does (one cached
    check either way — the registry-gate pattern)."""
    global _ENV_GATE
    if _ENV_GATE is _UNSET:
        flag = os.environ.get("MXNET_TPU_MEMWATCH")
        _ENV_GATE = None if flag is None else flag not in (
            "", "0", "false", "off")
    if _ENV_GATE is not None:
        return _ENV_GATE
    return _registry.is_armed()


def reset():
    """Drop tags, timeline, peak, leak/program state + cached env
    (tests); stops a running sampler thread."""
    global _ENV_GATE, _LEAK
    stop_sampler()
    with _TAG_LOCK:
        _TAGGED.clear()
    with _PROG_LOCK:
        _PROGRAMS.clear()
    _LAST_PROGRAM[0] = None
    _TIMELINE.clear()
    _PEAK[0] = 0.0
    _LAST_SAMPLE[0] = 0.0
    _LEAK = LeakWatchdog()      # re-reads MXNET_TPU_MEMWATCH_LEAK_MB
    _ENV_GATE = _UNSET


# ---------------------------------------------------------------------------
# tagging
# ---------------------------------------------------------------------------

def _device_leaves(tree):
    """Every jax-array-like leaf of a nested structure (NDArray wrappers
    are unwrapped to their device handle).  Host numpy is skipped — it
    is not HBM."""
    import weakref  # noqa: F401  (documents the ref story below)
    out = []
    stack = [tree]
    while stack:
        obj = stack.pop()
        if obj is None:
            continue
        if isinstance(obj, dict):
            stack.extend(obj.values())
        elif isinstance(obj, (list, tuple)):
            stack.extend(obj)
        else:
            handle = getattr(obj, "_handle", obj)
            # a live jax array: device-backed, deletable, sized
            if hasattr(handle, "is_deleted") and hasattr(handle, "nbytes"):
                out.append(handle)
    return out


def tag(tree, tag: str, label: str = ""):
    """Label every device buffer in ``tree`` with a taxonomy ``tag``
    (weakly — tagging never extends a buffer's lifetime).  Returns
    ``tree`` unchanged so call sites can wrap materialization
    expressions.  One cached-bool check when disarmed."""
    if not enabled():
        return tree
    import weakref
    bt = None
    if os.environ.get("MXNET_TPU_MEMWATCH_BACKTRACES", "0") not in (
            "0", "", "false", "off"):
        bt = "".join(traceback.format_stack(limit=10)[:-1])
    now = time.time()
    leaves = _device_leaves(tree)
    with _TAG_LOCK:
        for arr in leaves:
            try:
                ref = weakref.ref(arr)
            except TypeError:
                continue
            _TAGGED[id(arr)] = (ref, str(tag), str(label), now, bt)
        if len(_TAGGED) > 65536:
            _prune_locked()
    return tree


def _prune_locked():
    dead = [k for k, (ref, *_rest) in _TAGGED.items() if ref() is None]
    for k in dead:
        del _TAGGED[k]


def _tag_of(arr):
    entry = _TAGGED.get(id(arr))
    if entry is None:
        return None
    ref = entry[0]
    if ref() is not arr:        # id reused by a different object
        return None
    return entry


def release(tree) -> int:
    """Explicitly free the device buffers of ``tree`` (``Array.delete``)
    and return the bytes released.  The double-residency killer: call on
    the OLD state before materializing its replacement (checkpoint
    restore, model swap) so peak HBM stays ~1x instead of 2x.  Always
    active — an explicit free is never a probe."""
    freed = 0
    for arr in _device_leaves(tree):
        try:
            if not arr.is_deleted():
                freed += int(arr.nbytes)
                arr.delete()
        except Exception:       # committed/donated buffers: best effort
            continue
    return freed


# ---------------------------------------------------------------------------
# live accounting
# ---------------------------------------------------------------------------

def live_buffers(include_backtraces: bool = False) -> List[dict]:
    """Every live (undeleted) jax array in the process with its size and
    tag — the raw table the sampler, the OOM post-mortem, and
    ``tools/memwatch.py --top`` all read."""
    import jax
    now = time.time()
    out = []
    with _TAG_LOCK:
        for arr in jax.live_arrays():
            try:
                if arr.is_deleted() or not arr.nbytes:
                    continue
                row = {"nbytes": int(arr.nbytes),
                       "shape": list(arr.shape),
                       "dtype": str(arr.dtype),
                       "tag": "untagged", "label": ""}
            except Exception:
                continue
            entry = _tag_of(arr)
            if entry is not None:
                _ref, tg, label, created, bt = entry
                row["tag"] = tg
                row["label"] = label
                row["age_sec"] = round(now - created, 3)
                if include_backtraces and bt:
                    row["backtrace"] = bt
            out.append(row)
    return out


def top_buffers(n: int = 15, include_backtraces: bool = False) -> List[dict]:
    """The n largest live buffers, largest first."""
    rows = live_buffers(include_backtraces=include_backtraces)
    rows.sort(key=lambda r: -r["nbytes"])
    return rows[:n]


def live_bytes_by_tag() -> Dict[str, int]:
    """``{tag: live bytes}`` over every live array (untagged bucket
    included) plus ``"total"``."""
    out: Dict[str, int] = {}
    total = 0
    for row in live_buffers():
        out[row["tag"]] = out.get(row["tag"], 0) + row["nbytes"]
        total += row["nbytes"]
    out["total"] = total
    return out


def tagged_bytes(tag_name: str) -> int:
    """Live bytes currently carrying one tag (test/assert helper)."""
    return live_bytes_by_tag().get(tag_name, 0)


def device_memory_stats() -> Dict[str, dict]:
    """Per-device allocator stats where the backend reports them
    (``Device.memory_stats()`` — TPU/GPU; CPU returns none)."""
    import jax
    out = {}
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out[str(d.id)] = {
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
        }
    return out


def device_capacity_bytes() -> Optional[float]:
    """Per-device HBM capacity: the allocator's ``bytes_limit`` when the
    backend reports one, else the ``MXNET_TPU_DEVICE_HBM_GB`` override,
    else None (capacity checks disable themselves)."""
    stats = device_memory_stats()
    limits = [s["bytes_limit"] for s in stats.values()
              if s.get("bytes_limit")]
    if limits:
        return float(min(limits))
    gb = os.environ.get("MXNET_TPU_DEVICE_HBM_GB")
    if gb:
        try:
            return float(gb) * 1e9
        except ValueError:
            pass
    return None


# ---------------------------------------------------------------------------
# sampler: gauges + timeline + Perfetto counter track
# ---------------------------------------------------------------------------

def sample_now(step: Optional[int] = None) -> dict:
    """Take one memory sample: fold live bytes by tag into the registry
    gauges, advance the peak, append to the timeline, feed the leak
    watchdog, and emit the Perfetto counter event when the profiler
    runs.  Returns the by-tag dict.  Callers gate on :func:`enabled`."""
    by_tag = live_bytes_by_tag()
    total = by_tag.get("total", 0)
    _PEAK[0] = max(_PEAK[0], float(total))
    _LAST_SAMPLE[0] = time.time()
    _TIMELINE.append((_LAST_SAMPLE[0], total,
                      {k: v for k, v in by_tag.items() if k != "total"}))
    if _registry.is_armed():
        g = _registry.gauge("mem.live_bytes")
        for tg, b in by_tag.items():
            if tg == "total":
                continue
            g.set(float(b), tag=tg)
        _registry.set_gauge("mem.live_bytes_total", float(total))
        _registry.set_gauge("mem.peak_live_bytes", _PEAK[0])
        for dev, stats in device_memory_stats().items():
            _registry.set_gauge("mem.device_bytes_in_use",
                                float(stats["bytes_in_use"]), device=dev)
    from .. import profiler
    if profiler.is_running():
        args = {"total": total}
        args.update({k: v for k, v in by_tag.items() if k != "total"})
        profiler.record_counter("memory/live_bytes", args)
    _LEAK.observe(step, total)
    return by_tag


def note_step(step: Optional[int] = None, min_interval: float = 0.25):
    """Throttled per-step/per-request sample + leak check — the
    synchronous seam trainers and the serving loop tick (no thread
    needed for the timeline to fill).  One cached-bool check when
    disarmed."""
    if not enabled():
        return
    now = time.time()
    if now - _LAST_SAMPLE[0] < min_interval:
        return
    sample_now(step=step)


def maybe_start_sampler():
    """Start the daemon sampler thread once (armed processes only)."""
    global _SAMPLER
    if not enabled():
        return
    if _SAMPLER is not None and _SAMPLER.is_alive():
        return
    interval = _env_float("MXNET_TPU_MEMWATCH_INTERVAL", 1.0)
    _SAMPLER_STOP.clear()

    def run():
        while not _SAMPLER_STOP.wait(timeout=max(0.05, interval)):
            if not enabled():
                continue
            try:
                sample_now()
            except Exception:
                logging.exception("memwatch sampler failed (continuing)")

    _SAMPLER = threading.Thread(target=run, name="mxt-memwatch",
                                daemon=True)
    _SAMPLER.start()


def stop_sampler():
    global _SAMPLER
    _SAMPLER_STOP.set()
    t = _SAMPLER
    if t is not None and t.is_alive():
        t.join(timeout=2.0)
    _SAMPLER = None


def memory_window(seconds: float = 30.0) -> dict:
    """The last-N-seconds memory timeline (the block an OOM post-mortem
    embeds): samples of (t, total, by_tag), plus peak-so-far."""
    now = time.time()
    samples = [{"t": t, "total_bytes": total, "by_tag": by_tag}
               for t, total, by_tag in list(_TIMELINE)
               if now - t <= seconds]
    return {"seconds": seconds, "samples": samples,
            "peak_live_bytes": _PEAK[0]}


def peak_live_bytes() -> float:
    return _PEAK[0]


def measured_snapshot() -> Optional[dict]:
    """The measured side the attribution report's memory section embeds
    (None when the plane is disarmed or never sampled)."""
    if not enabled():
        return None
    by_tag = sample_now()
    return {"live_bytes": by_tag.get("total", 0),
            "peak_live_bytes": _PEAK[0],
            "by_tag": {k: v for k, v in by_tag.items() if k != "total"}}


# ---------------------------------------------------------------------------
# per-program memory registry (feeds attribution + OOM forensics)
# ---------------------------------------------------------------------------

def note_program(name: str, compiled=None, breakdown: Optional[dict] = None):
    """Record a compiled program's memory breakdown so an OOM can report
    the footprint of the program that tripped.  ``breakdown`` wins when
    given; else ``compiled.memory_analysis()`` is normalized via
    :func:`~mxnet_tpu.analysis.costmodel.memory_breakdown`.  Never
    raises."""
    try:
        if breakdown is None and compiled is not None:
            from ..analysis import costmodel
            breakdown = costmodel.memory_breakdown(compiled)
        with _PROG_LOCK:
            if breakdown:
                _PROGRAMS[str(name)] = dict(breakdown)
            _LAST_PROGRAM[0] = str(name)
    except Exception:
        logging.debug("note_program(%s) failed", name, exc_info=True)


def program_memory(name: Optional[str] = None) -> Optional[dict]:
    """The recorded breakdown for ``name`` (or the most recently noted
    program when None)."""
    with _PROG_LOCK:
        if name is None:
            name = _LAST_PROGRAM[0]
        if name is None:
            return None
        bd = _PROGRAMS.get(str(name))
        return dict(bd) if bd else None


# ---------------------------------------------------------------------------
# leak watchdog
# ---------------------------------------------------------------------------

class LeakWatchdog:
    """Flags monotonic live-bytes growth across steps/requests — the
    classic unbounded-cache shape: every sample higher than the last,
    total growth past the threshold.  A healthy training loop plateaus
    after warm-up (donated buffers reuse HBM); a leak never does."""

    def __init__(self, window: int = 16, min_samples: int = 8,
                 threshold_bytes: Optional[float] = None):
        self.window = int(window)
        self.min_samples = int(min_samples)
        self.threshold_bytes = (
            _env_float("MXNET_TPU_MEMWATCH_LEAK_MB", 64.0) * 1e6
            if threshold_bytes is None else float(threshold_bytes))
        self._samples: deque = deque(maxlen=self.window)
        self._flagged = False
        self._lock = threading.Lock()

    def reset(self):
        with self._lock:
            self._samples.clear()
            self._flagged = False

    def observe(self, step, total_bytes):
        with self._lock:
            self._samples.append((step, float(total_bytes)))

    def check(self) -> Optional[dict]:
        """A report dict when the window shows a leak, else None."""
        with self._lock:
            samples = list(self._samples)
        if len(samples) < self.min_samples:
            return None
        values = [b for _s, b in samples]
        growth = values[-1] - values[0]
        monotonic = all(b2 >= b1 for b1, b2 in zip(values, values[1:]))
        strictly_up = sum(1 for b1, b2 in zip(values, values[1:])
                          if b2 > b1)
        if not (monotonic and growth > self.threshold_bytes
                and strictly_up >= self.min_samples // 2):
            return None
        report = {
            "kind": "leak_suspected",
            "samples": len(values),
            "growth_bytes": int(growth),
            "growth_per_sample_bytes": int(growth / max(1, len(values) - 1)),
            "first_bytes": int(values[0]),
            "last_bytes": int(values[-1]),
            "steps": [s for s, _b in samples],
            "threshold_bytes": int(self.threshold_bytes),
        }
        with self._lock:
            if not self._flagged:
                self._flagged = True
                logging.warning(
                    "memwatch: live bytes grew monotonically by %.1f MB "
                    "over the last %d samples — suspected leak (top "
                    "growers: run tools/memwatch.py --top against the "
                    "telemetry feed)", growth / 1e6, len(values))
        _registry.set_gauge("mem.leak_growth_bytes", float(growth))
        _registry.count("mem.leak_suspected")
        return report


_LEAK = LeakWatchdog()


def leak_report() -> Optional[dict]:
    """The process leak-watchdog's verdict over its rolling window."""
    return _LEAK.check()


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OOM when allocating")


def is_oom(exc: BaseException) -> bool:
    """Does this exception look like a device allocator failure?"""
    if isinstance(exc, MemoryError):
        return True
    text = "%s: %s" % (type(exc).__name__, exc)
    return any(m in text for m in _OOM_MARKERS)


def _hint(by_tag: Dict[str, int], prog_mem: Optional[dict]) -> str:
    """One actionable sentence from the evidence: which bucket dominates
    and what the fix menu for that bucket is (the GC202/GC501 playbook)."""
    buckets = {k: v for k, v in by_tag.items()
               if k not in ("total",) and v > 0}
    top = max(buckets, key=buckets.get) if buckets else "untagged"
    hints = {
        "activations": "activations dominate: enable gradient remat "
                       "(backward_mirror_policy) or cut the microbatch",
        "batch": "input batches dominate: reduce the global batch or "
                 "feed in chunks",
        "optimizer": "optimizer state dominates: shard it over dp "
                     "(ShardedTrainer(shard_optimizer_state=True), "
                     "ZeRO-style)",
        "params": "parameters dominate: shard over a tp axis "
                  "(__shard__ attrs) or load in lower precision",
        "served": "served models dominate: unload replicas or roll the "
                  "swap back (ServingRuntime.rollback)",
        "untagged": "most live bytes are untagged: run with "
                    "MXNET_TPU_MEMWATCH_BACKTRACES=1 to find the "
                    "allocation sites",
    }
    hint = hints.get(top, hints["untagged"])
    if prog_mem and not prog_mem.get("alias_bytes"):
        hint += ("; the tripping program aliases no buffers — check "
                 "donation (tpulint --graphcheck, rule GC202)")
    return hint


def _report_dir() -> str:
    from ..resilience import watchdog as _wd
    return (os.environ.get("MXNET_TPU_WATCHDOG_DIR")
            or _wd.default_report_dir()
            or os.getcwd())


def write_oom_postmortem(tag_name: str, exc: BaseException,
                         program: Optional[str] = None,
                         step=None, report_dir: Optional[str] = None
                         ) -> Optional[str]:
    """Write the OOM post-mortem JSON into the standard forensics dir;
    returns the path (None on total failure — forensics must never mask
    the original error)."""
    try:
        d = report_dir or _report_dir()
        os.makedirs(d, exist_ok=True)
        try:
            import jax
            rank = jax.process_index()
        except Exception:
            rank = 0
        _OOM_SEQ[0] += 1
        topk = int(_env_float("MXNET_TPU_MEMWATCH_TOPK", 15))
        with_bt = os.environ.get("MXNET_TPU_MEMWATCH_BACKTRACES",
                                 "0") not in ("0", "", "false", "off")
        by_tag = live_bytes_by_tag()
        prog_mem = program_memory(program)
        report = {
            "kind": "oom_postmortem",
            "tag": tag_name,
            "step": step,
            "rank": rank,
            "pid": os.getpid(),
            "time": time.time(),
            "error": "%s: %s" % (type(exc).__name__, exc),
            "program": program or _LAST_PROGRAM[0],
            "program_memory": prog_mem,
            "live_bytes_by_tag": by_tag,
            "top_buffers": top_buffers(topk, include_backtraces=with_bt),
            "device_memory": device_memory_stats(),
            "capacity_bytes": device_capacity_bytes(),
            "timeline": memory_window(),
            "leak": leak_report(),
            "hint": _hint(by_tag, prog_mem),
        }
        try:
            report["metrics_window"] = (_registry.metrics_window()
                                        if _registry.is_armed() else None)
        except Exception:
            report["metrics_window"] = None
        path = os.path.join(d, "%s-r%d-%d-%d.json"
                            % (_POSTMORTEM_PREFIX, rank, os.getpid(),
                               _OOM_SEQ[0]))
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2, default=repr)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        logging.error("memwatch: RESOURCE_EXHAUSTED in %s — OOM "
                      "post-mortem: %s", tag_name, path)
        return path
    except Exception:
        logging.exception("memwatch: OOM post-mortem write failed")
        return None


@contextmanager
def oom_guard(tag_name: str, program: Optional[str] = None, step=None):
    """Wrap a watchdog-armed dispatch region so a RESOURCE_EXHAUSTED
    writes a post-mortem before re-raising.  Hot-path cost: one
    try/except frame — no gate needed (the handler only runs while the
    process is dying of an OOM, and the report is cheap next to the
    re-compile any recovery implies)."""
    try:
        yield
    except BaseException as e:
        if is_oom(e):
            _registry.count("mem.oom", tag=tag_name)
            write_oom_postmortem(tag_name, e, program=program, step=step)
        raise
