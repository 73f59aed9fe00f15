"""Persistent compiled-executable cache: recovery without recompilation.

Every mesh re-form (elastic 4→3→4), fleet swap, and replica relaunch
used to pay full XLA compilation at the worst possible moment — right
after losing a rank, or mid-rollout.  This cache makes recovery time
independent of compile time: a compiled (and serialized) executable is
persisted in the resilience container format (JSON header + raw
buffers + CRC32s, resilience/container.py — the checkpoint discipline:
atomic rename, no pickle in the header, corruption detectable before
anything is interpreted) and any later process with a matching key
deserializes it instead of compiling.

The key is exact, mirroring ``ops/autotune.py``'s keying philosophy:

* **program fingerprint** — sha256 of the lowered StableHLO text, which
  captures the program, shapes, dtypes, shardings AND the donation/
  layout signature (donated args appear as aliasing attributes in the
  lowered module).  Identical text ⇒ interchangeable executable.
* **device signature** — platform / device kind / the exact device ids
  the program's mesh spans (an executable bakes its device assignment;
  tuned code must never leak across chip generations).
* **jax version + backend** — serialized executables are not stable
  across runtime upgrades.

A cache entry that fails validation — truncated, bit-flipped, CRC
mismatch, a key that does not match its content, or an executable XLA
refuses to deserialize — is **quarantined** (renamed ``*.corrupt``) and
the caller falls back to a fresh compile: degraded, never wrong.  The
``compile.cache{result=...}`` counter and the ``result=`` tag on
``compile/*`` spans make every outcome provable from telemetry.

Programs whose lowered module calls back into the host (pure_callback,
pallas interpret mode, debug prints) are *uncacheable*: a deserialized
callback descriptor would point at a function that does not exist in
the loading process.  They are detected by scanning the lowered text
and simply never persisted (``result=uncacheable``).

Knobs (docs/robustness.md):

=====================================  ====================================
``MXNET_TPU_COMPILE_CACHE``            ``1`` enables at the default
                                       location (``<checkout>/.cache/
                                       compile-cache``); a path selects a
                                       directory; ``0``/unset disables
``MXNET_TPU_COMPILE_CACHE_MAX_MB``     best-effort size bound: oldest
                                       entries beyond it are pruned after
                                       a store (default 512)
=====================================  ====================================
"""
from __future__ import annotations

import contextlib
import hashlib
import logging
import os
import time
from typing import Optional, Sequence, Tuple

from . import paths as _paths
from .treedefs import UnsupportedTreedef, obj_to_treedef, treedef_to_obj

__all__ = ["enabled", "arm", "disarm", "cache_dir", "entry_path",
           "outside_jax_cache",
           "program_fingerprint", "program_devices", "device_signature",
           "cached_compile",
           "donation_safe", "load", "store", "quarantine", "cache_stats",
           "clear", "CACHE_MAGIC"]

CACHE_MAGIC = "mxnet_tpu-compile-cache-v1"
_ENV = "MXNET_TPU_COMPILE_CACHE"
_ARMED: Optional[bool] = None       # programmatic override (tests/drills)
_ARMED_DIR: Optional[str] = None

# lowered-text markers of host round-trips that cannot survive
# serialization into another process (callback ids are process-local)
_UNCACHEABLE_MARKERS = ("callback", "infeed", "outfeed", "debug_print")

# lowered-text markers of input→output aliasing (donated buffers)
_ALIASING_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


@contextlib.contextmanager
def outside_jax_cache():
    """Scope in which jax's persistent compilation cache is neither read
    nor written — for programs that carry non-default layouts.

    On the installed jax 0.9.0 / libtpu 0.0.34 an executable that comes
    OUT of that cache has forgotten its result layouts: a ``device_put`` to
    a ``Format`` returns the default layout on a cache hit (measured on
    the v5e, PERF.md PR 21), so a warm process would hand the AUTO-layout
    train step arrays it then refuses.  jax decides once per process
    whether it uses the cache, hence the resets around the switch; another
    thread that compiles meanwhile only misses the cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def donation_safe(backend: Optional[str] = None) -> bool:
    """Whether serialized executables with donated (aliased) inputs are
    trustworthy on this backend.  XLA:CPU never implemented buffer
    donation (jax strips it at load with a warning), but a DESERIALIZED
    CPU executable re-applies the aliasing without the runtime support
    and computes wrong results (proven by test_compile_cache.py's
    donated-round-trip test).  So on CPU the cache refuses donated
    entries outright, and the trainer builds its step donation-free
    while the cache is armed — identical numerics and cost there, since
    the runtime was ignoring the donation anyway."""
    import jax
    return (backend or jax.default_backend()) not in ("cpu",)


def arm(directory: Optional[str] = None):
    """Enable the cache for this process (tests/drills; env wins for
    child processes — export ``MXNET_TPU_COMPILE_CACHE`` for gangs)."""
    global _ARMED, _ARMED_DIR
    _ARMED = True
    if directory is not None:
        _ARMED_DIR = os.fspath(directory)


def disarm():
    global _ARMED, _ARMED_DIR
    _ARMED = False
    _ARMED_DIR = None


def reset():
    """Back to env-driven state (tests)."""
    global _ARMED, _ARMED_DIR
    _ARMED = None
    _ARMED_DIR = None


def enabled() -> bool:
    """Opt-in: armed programmatically, or ``MXNET_TPU_COMPILE_CACHE``
    set to ``1``/a directory.  Off by default — executables land on
    disk only when an operator (or a drill) asked for them."""
    if _ARMED is not None:
        return _ARMED
    raw = os.environ.get(_ENV, "").strip()
    return bool(raw) and raw.lower() not in _paths.ENV_OFF


def cache_dir() -> Optional[str]:
    if _ARMED and _ARMED_DIR:
        return _ARMED_DIR
    return _paths.cache_location(_ENV, "compile-cache")


def _count(result: str, what: str = ""):
    from .. import telemetry
    telemetry.count("compile.cache", result=result, what=what or "unknown")


# ---------------------------------------------------------------------------
# keying
# ---------------------------------------------------------------------------

def program_fingerprint(lowered_text: str) -> str:
    """sha256 of the lowered StableHLO text — the exact program identity
    (shapes, dtypes, shardings, donation aliasing all included).  The
    text is identical across processes for the same program, so a
    standby compiled at world N matches the first step at world N−1."""
    return hashlib.sha256(lowered_text.encode("utf-8")).hexdigest()


def program_devices(mesh=None) -> list:
    """The devices an executable binds to, in assignment order: the
    mesh's for a program lowered under one, else the default device.  A
    deserialized executable must be told — jax would otherwise bind it to
    every local device and reject its arguments ("expected 8 shards, got
    1") on any host with more than one."""
    import jax
    if mesh is not None:
        return list(mesh.devices.flat)
    return jax.devices()[:1]


def device_signature(mesh=None) -> str:
    """platform / kind / exact device ids the executable will bind to."""
    import jax
    devices = program_devices(mesh)
    kinds = sorted({str(d.device_kind) for d in devices})
    ids = ",".join(str(d.id) for d in devices)
    return "%s|%s|%s" % (jax.default_backend(), "+".join(kinds), ids)


def _key_digest(fingerprint: str, device_sig: str,
                extra: Sequence = ()) -> str:
    import jax
    parts = [CACHE_MAGIC, fingerprint, device_sig, jax.__version__]
    parts.extend(str(e) for e in extra)
    return hashlib.sha256("\x1f".join(parts).encode("utf-8")).hexdigest()


def entry_path(key_digest: str) -> Optional[str]:
    d = cache_dir()
    if d is None:
        return None
    return os.path.join(d, "cc-%s.mxc" % key_digest[:32])


# ---------------------------------------------------------------------------
# entry I/O
# ---------------------------------------------------------------------------

def quarantine(path: str, reason: str, what: str = "") -> None:
    """Move a bad entry out of the lookup path (``*.corrupt``) so it can
    be inspected but never loaded again; never raises."""
    try:
        os.replace(path, path + ".corrupt")
    except OSError:
        try:                        # last resort: make it unloadable
            os.unlink(path)
        except OSError:
            pass
    logging.warning("compile-cache: quarantined %s (%s)", path, reason)
    _count("corrupt" if reason.startswith("corrupt") else reason, what)


def load(key_digest: str, what: str = "", mesh=None):
    """Deserialize the entry for ``key_digest`` onto the devices of
    ``mesh`` (:func:`program_devices`) or return None (miss,
    corrupt-quarantined, key-mismatch-quarantined, or deserializer
    refusal — every non-hit degrades to 'caller compiles fresh')."""
    path = entry_path(key_digest)
    if path is None or not os.path.exists(path):
        return None
    from ..resilience import chaos
    from ..resilience.container import CorruptContainer, read_container
    fault = chaos.fire("corrupt_compile_cache")
    if fault is not None:
        _damage_entry(path, mode=fault.get("mode", "garbage"))
    try:
        arrays, meta, blobs = read_container(path)
    except (CorruptContainer, OSError) as e:
        quarantine(path, "corrupt: %s" % e, what)
        return None
    try:
        if meta.get("magic") != CACHE_MAGIC or meta.get("key") != key_digest:
            # a hash collision or a foreign file under our name: treat
            # exactly like corruption — a wrong executable must be
            # structurally unreachable, not merely unlikely
            quarantine(path, "mismatch", what)
            return None
        from jax.experimental import serialize_executable
        in_tree = obj_to_treedef(meta["in_tree"])
        out_tree = obj_to_treedef(meta["out_tree"])
        compiled = serialize_executable.deserialize_and_load(
            blobs["executable"], in_tree, out_tree,
            execution_devices=program_devices(mesh))
    except Exception as e:
        quarantine(path, "corrupt: deserialize failed: %r" % e, what)
        return None
    _count("hit", what)
    return compiled


def store(key_digest: str, compiled, lowered_text: str, what: str = "",
          device_sig: str = "", compile_seconds: Optional[float] = None
          ) -> Optional[str]:
    """Serialize ``compiled`` into the cache (atomic container write).
    Returns the entry path, or None when the program is uncacheable or
    serialization fails — both are safe non-events, never errors."""
    path = entry_path(key_digest)
    if path is None:
        return None
    low = lowered_text.lower()
    if any(m in low for m in _UNCACHEABLE_MARKERS):
        _count("uncacheable", what)
        return None
    if not donation_safe() and any(m.lower() in low
                                   for m in _ALIASING_MARKERS):
        _count("uncacheable", what)
        return None
    try:
        from jax.experimental import serialize_executable
        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        meta = {
            "magic": CACHE_MAGIC,
            "key": key_digest,
            "what": what,
            "fingerprint": program_fingerprint(lowered_text),
            "device_sig": device_sig,
            "in_tree": treedef_to_obj(in_tree),
            "out_tree": treedef_to_obj(out_tree),
            "compile_seconds": (round(float(compile_seconds), 6)
                                if compile_seconds is not None else None),
            "created": time.time(),
        }
        from ..resilience.container import write_container
        os.makedirs(os.path.dirname(path), exist_ok=True)
        write_container(path, meta=meta, blobs={"executable": payload})
    except UnsupportedTreedef:
        _count("uncacheable", what)
        return None
    except Exception:
        logging.exception("compile-cache: store failed for %s (continuing "
                          "uncached)", what)
        _count("store_failed", what)
        return None
    _prune()
    return path


def _damage_entry(path: str, mode: str = "garbage"):
    """Chaos ``corrupt_compile_cache`` implementation: damage the entry
    in place the way bit rot / a torn copy would, so the load path's
    validation — not a mock — is what the drill proves."""
    try:
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            if mode == "truncate":
                f.truncate(max(16, size // 2))
            else:                   # bit-flip inside a buffer
                f.seek(max(16, size // 2))
                f.write(b"\xde\xad\xbe\xef" * 8)
            f.flush()
            os.fsync(f.fileno())
        logging.warning("chaos: corrupted compile-cache entry %s (%s)",
                        path, mode)
    except OSError:
        pass


def _prune():
    """Best-effort size bound: drop oldest entries past
    ``MXNET_TPU_COMPILE_CACHE_MAX_MB`` (default 512)."""
    d = cache_dir()
    if d is None:
        return
    try:
        limit = float(os.environ.get("MXNET_TPU_COMPILE_CACHE_MAX_MB",
                                     "512")) * (1 << 20)
        entries = []
        total = 0
        for name in os.listdir(d):
            if not (name.startswith("cc-") and name.endswith(".mxc")):
                continue
            p = os.path.join(d, name)
            st = os.stat(p)
            entries.append((st.st_mtime, st.st_size, p))
            total += st.st_size
        entries.sort()
        while total > limit and entries:
            _, size, p = entries.pop(0)
            os.unlink(p)
            total -= size
    except OSError:
        pass


def clear():
    """Delete every entry (tests)."""
    d = cache_dir()
    if d is None:
        return
    try:
        for name in os.listdir(d):
            if name.startswith("cc-"):
                os.unlink(os.path.join(d, name))
    except OSError:
        pass


def cache_stats() -> dict:
    """Filesystem-level view for tooling: entry/corrupt counts, bytes."""
    d = cache_dir()
    out = {"dir": d, "entries": 0, "bytes": 0, "quarantined": 0}
    if d is None or not os.path.isdir(d):
        return out
    for name in os.listdir(d):
        p = os.path.join(d, name)
        if name.startswith("cc-") and name.endswith(".mxc"):
            out["entries"] += 1
            try:
                out["bytes"] += os.path.getsize(p)
            except OSError:
                pass
        elif name.endswith(".corrupt"):
            out["quarantined"] += 1
    return out


# ---------------------------------------------------------------------------
# the one-stop API
# ---------------------------------------------------------------------------

def cached_compile(lowered, what: str, mesh=None, extra: Sequence = (),
                   standby: bool = False) -> Tuple[object, str]:
    """Compile ``lowered`` through the cache: returns ``(compiled,
    result)`` with ``result`` in ``hit`` (deserialized, zero compile) /
    ``miss`` (fresh compile, written through) / ``standby`` (a miss
    taken deliberately by the background pre-compiler) / ``off`` (cache
    disabled).  Every failure mode inside the cache degrades to a fresh
    compile."""
    if not enabled():
        return lowered.compile(), "off"
    try:
        text = lowered.as_text()
        dev_sig = device_signature(mesh)
        # `what` is part of the key: two call sites lowering to the same
        # text but calling differently (e.g. an AUTO-layout build whose
        # layout request is not visible in the module text) must never
        # share an entry
        key = _key_digest(program_fingerprint(text), dev_sig,
                          (what,) + tuple(extra))
    except Exception:
        logging.exception("compile-cache: keying failed for %s "
                          "(compiling uncached)", what)
        return lowered.compile(), "off"
    hit = load(key, what=what, mesh=mesh)
    if hit is not None:
        return hit, "hit"
    from .. import telemetry as _tel
    with _tel.span("compile/xla", cat="compile", timed=True) as _sp:
        compiled = lowered.compile()
    store(key, compiled, text, what=what, device_sig=dev_sig,
          compile_seconds=_sp.duration)
    result = "standby" if standby else "miss"
    _count(result, what)
    return compiled, result
