"""Measure-and-cache autotuner for Pallas kernel block sizes.

TVM's measure-driven schedule search (PAPERS.md, arXiv:1802.04799)
scaled down to the knobs that matter on this codebase: the flash
attention forward/backward block sizes.  The right (block_q, block_k)
depends on sequence length, head dim, dtype and chip generation in ways
no static rule captures (the r4 table showed 1.0x-1.8x swings between
shapes at FIXED blocks) — so the tuner *measures* candidates on the real
device, remembers the winner in a persisted JSON cache keyed by
``(op, shape-sig, dtype, device_kind)``, and every later run — any
process, any day — gets the tuned blocks for free.

Separation of concerns:

* :func:`flash_blocks` — the READ side.  Called from the kernel wrappers
  (``ops/pallas_kernels._pick_blocks``) at trace time: cache hit or
  static default, never measures, never touches the device (safe under
  jit tracing).
* :func:`autotune` — the generic WRITE side: candidates + a measure
  callable -> winner, cached.  Measurement only runs when
  ``MXNET_TPU_AUTOTUNE=1`` (or ``force=True``); each trial is wrapped in
  a ``autotune/trial`` telemetry span feeding the ``autotune.trial_
  seconds`` histogram, so the search itself shows up on the PR-5
  measurement plane and in the merged trace.
* :func:`tune_flash` — the flash-specific search driver
  (``tools/bench_pallas.py --autotune`` runs it on-chip and ships the
  cache).

Knobs (docs/observability.md):

=====================================  ====================================
``MXNET_TPU_AUTOTUNE``                 ``1`` enables measuring in
                                       :func:`autotune`/:func:`tune_flash`
                                       (default: cache/defaults only)
``MXNET_TPU_AUTOTUNE_CACHE``           cache file (default
                                       ``<checkout>/.cache/autotune-
                                       <device_kind>.json``)
=====================================  ====================================
"""
from __future__ import annotations

import functools
import json
import logging
import os
import threading
import time
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

__all__ = ["flash_blocks", "autotune", "tune_flash", "lookup", "record",
           "cache_path", "invalidate", "device_kind",
           "DEFAULT_FLASH_BLOCKS", "decode_backend", "tune_decode"]

# static fallbacks when the cache has no entry, which is how a fresh
# checkout runs: the winners of a v5e sweep at the benchmark's training
# shape (192 heads x 1,024 x 64, bfloat16, causal; PERF.md section 6,
# PR 27), each kernel within 10% of them from (512, 512) up.
# ``pallas_kernels._fit_block`` fits them to other lengths.
DEFAULT_FLASH_BLOCKS = {"fwd": (512, 1024), "bwd": (512, 1024)}

_LOCK = threading.RLock()
_CACHE: Optional[Dict[str, dict]] = None
_CACHE_FROM: Optional[str] = None


def device_kind() -> str:
    """Sanitized accelerator kind for the cache key/filename — tuned
    blocks must never leak across chip generations (or from the
    interpret-mode CPU path onto a real TPU)."""
    try:
        import jax
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = "unknown"
    return "".join(c if c.isalnum() or c in "-_." else "-"
                   for c in str(kind).lower()) or "unknown"


def cache_path() -> str:
    # the shared cache-location rule (compile/paths.py): env override
    # wins, else <checkout>/.cache/ — the same convention the compiled-
    # executable cache follows, so MXNET_TPU_*_CACHE knobs behave
    # identically across both
    from ..compile import paths as _paths
    return _paths.cache_location(
        "MXNET_TPU_AUTOTUNE_CACHE",
        "autotune-%s.json" % device_kind()) or os.path.join(
        _paths.cache_root(), "autotune-%s.json" % device_kind())


def _load() -> Dict[str, dict]:
    global _CACHE, _CACHE_FROM
    path = cache_path()
    with _LOCK:
        if _CACHE is not None and _CACHE_FROM == path:
            return _CACHE
        data: Dict[str, dict] = {}
        try:
            with open(path) as f:
                raw = json.load(f)
            if isinstance(raw, dict):
                data = {k: v for k, v in raw.items()
                        if isinstance(v, dict) and "config" in v}
        except (OSError, ValueError):
            pass
        _CACHE = data
        _CACHE_FROM = path
        return data


def _save() -> None:
    path = cache_path()
    with _LOCK:
        data = dict(_CACHE or {})
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError:
        pass                       # a read-only home must not break runs


def invalidate() -> None:
    """Drop the in-process cache (tests; after an external cache write)."""
    global _CACHE, _CACHE_FROM
    with _LOCK:
        _CACHE = None
        _CACHE_FROM = None


def _key(op: str, sig: Sequence) -> str:
    return "%s:%s" % (op, ",".join(str(s) for s in sig))


def lookup(op: str, sig: Sequence) -> Optional[dict]:
    """Cached entry ``{"config", "score_ms", ...}`` or None.  Pure cache
    read — safe at trace time."""
    return _load().get(_key(op, sig))


def record(op: str, sig: Sequence, config, score_ms: float,
           trials: int = 0) -> dict:
    """Persist a winner (atomic rewrite of the whole cache file)."""
    entry = {"config": (list(config) if isinstance(config, (list, tuple))
                        else config),
             "score_ms": round(float(score_ms), 4),
             "trials": int(trials), "device_kind": device_kind(),
             "t": time.time()}
    with _LOCK:
        _load()[_key(op, sig)] = entry
    _save()
    return entry


def measuring_enabled() -> bool:
    return os.environ.get("MXNET_TPU_AUTOTUNE", "0") == "1"


def autotune(op: str, sig: Sequence, candidates: Iterable,
             measure: Callable[[object], float], default=None,
             force: bool = False, lower: Optional[Callable] = None):
    """Generic search: return the cached winner for ``(op, sig)`` or —
    when measuring is enabled — time every candidate with ``measure``
    (seconds per call; smaller is better), cache the winner, and return
    it.  With measuring disabled and no cache entry, returns
    ``default`` (or the first candidate).

    ``lower``: optional ``cand -> jax Lowered``.  When given, each
    candidate is compiled THROUGH the persistent executable cache
    (mxnet_tpu/compile) before measuring and ``measure`` is called as
    ``measure(cand, compiled)`` — so a re-tune (new shapes sweep, a
    relaunched tuning job) pays zero compilation for candidates any
    earlier run already built.

    A candidate whose measurement RAISES is skipped with a warning that
    carries the compiler's message (an over-budget block config that
    fails to compile is data for a search).  If EVERY candidate fails,
    the fallback cannot run either — that is raised, not returned."""
    hit = lookup(op, sig)
    if hit is not None:
        return tuple(hit["config"]) if isinstance(hit["config"], list) \
            else hit["config"]
    cands = list(candidates)
    fallback = default if default is not None else (
        cands[0] if cands else None)
    if not (measuring_enabled() or force) or not cands:
        return fallback
    from .. import telemetry as _tel
    best, best_s = None, None
    trials = 0
    last_error = None
    for cand in cands:
        with _tel.span("autotune/trial", cat="autotune",
                       metric="autotune.trial_seconds", op=op,
                       config=str(cand)):
            try:
                # a trial's cost is dominated by compiling the candidate
                # block config — it belongs to the compile/ span family
                cc_result = None
                with _tel.span("compile/autotune_trial", cat="compile",
                               metric="compile.seconds", timed=True,
                               op=op) as _cs:
                    if lower is not None:
                        from .. import compile as _cc
                        built, cc_result = _cc.cached_compile(
                            lower(cand), "autotune_trial",
                            extra=(op, str(cand)))
                        _cs.attrs["result"] = cc_result
                        dt = float(measure(cand, built))
                    else:
                        dt = float(measure(cand))
            except Exception as e:
                _tel.count("autotune.failed_trials", op=op)
                logging.warning("autotune %s%s: candidate %s failed: %s",
                                op, tuple(sig), cand, e)
                last_error = e
                continue
        _tel.tracing.note_compile(
            "autotune_trial", _cs.duration, op=op,
            **({"result": cc_result} if cc_result else {}))
        trials += 1
        _tel.count("autotune.trials", op=op)
        if best_s is None or dt < best_s:
            best, best_s = cand, dt
    if best is None:
        raise RuntimeError(
            "autotune %s%s: every candidate failed %s"
            % (op, tuple(sig), cands)) from last_error
    record(op, sig, best, best_s * 1e3, trials=trials)
    return best


# ---------------------------------------------------------------------------
# flash attention block sizes
# ---------------------------------------------------------------------------

def _flash_sig(kind: str, Tq: int, Tk: int, D: int, dtype) -> Tuple:
    return (kind, int(Tq), int(Tk), int(D), str(dtype))


def flash_blocks(kind: str, Tq: int, Tk: int, D: int = 0,
                 dtype: str = "") -> Tuple[int, int]:
    """(block_q, block_k) for the flash ``kind`` in {"fwd", "bwd"}:
    cache hit, else the static default.  Read-only — called from kernel
    wrappers at trace time."""
    hit = lookup("flash_%s" % kind, _flash_sig(kind, Tq, Tk, D, dtype))
    if hit is not None:
        bq, bk = hit["config"]
        return int(bq), int(bk)
    return DEFAULT_FLASH_BLOCKS[kind]


def _flash_candidates(kind: str, Tq: int, Tk: int, D: int,
                      itemsize: int = 2):
    """Block-size grid, pre-filtered by a VMEM budget.  A cell holds its
    blocks in the operands' own dtype (``itemsize`` bytes; the kernels no
    longer widen them), each twice because the grid pipeline double-
    buffers every block it copies, a (rows, D) block with D padded to
    whole 128-lane registers; float32 accumulators; and a handful of
    float32 (sub, block_q) score tiles, ``sub`` being the kernels' inner
    key sub-tile.  Candidates past ~12 MB of the 16 MB a kernel gets can
    only fail to compile."""
    from .pallas_kernels import _FLASH_SUB_K
    budget = 12 * (1 << 20)
    lanes = -(-D // 128) * 128
    out = []
    for bq in (128, 256, 512, 1024):
        for bk in (128, 256, 512, 1024, 2048):
            if bq > Tq or bk > Tk:
                continue
            q_rows = 2 * bq * lanes * itemsize      # q, do: (bq, D)
            k_rows = 2 * bk * lanes * itemsize      # k, v, dk, dv: (bk, D)
            q_cols = 2 * D * bq * itemsize          # out, dq: (D, bq)
            k_cols = 2 * D * bk * itemsize          # v or k transposed
            if kind == "fwd":       # q, k, v^T -> out^T; acc
                held = q_rows + k_rows + k_cols + q_cols + D * bq * 4
            else:                   # the larger of flash_bwd_dq and _dkv
                held = max(2 * q_rows + 2 * k_rows + k_cols + q_cols
                           + D * bq * 4,
                           2 * q_rows + 4 * k_rows + 2 * bk * lanes * 4)
            scores = 4 * bq * min(bk, _FLASH_SUB_K) * 4
            if held + scores <= budget:
                out.append((bq, bk))
    return out or [DEFAULT_FLASH_BLOCKS[kind]]


def tune_flash(q, k, v, causal: bool = True, kinds=("fwd", "bwd"),
               iters: int = 10, force: bool = False) -> Dict[str, tuple]:
    """Search flash block sizes for these exact operand shapes on the
    current device and persist the winners.  Timing is a call chain
    ended by block_until_ready.
    Returns ``{kind: (bq, bk)}``."""
    import jax
    import jax.numpy as jnp
    from . import pallas_kernels as pk
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    results = {}

    def timed(fn):
        def run(cand):
            bq, bk = cand
            out = None
            for _ in range(3):
                out = fn(bq, bk)
            jax.block_until_ready(out)
            from .. import telemetry as _tel
            with _tel.span("autotune/measure", cat="autotune",
                           timed=True) as sp:
                for _ in range(iters):
                    out = fn(bq, bk)
                jax.block_until_ready(out)
            return sp.duration / iters
        return run

    if "fwd" in kinds:
        def fwd(bq, bk):
            return pk.fused_attention_fwd(q, k, v, causal=causal,
                                          block_q=bq, block_k=bk)
        results["fwd"] = autotune(
            "flash_fwd", _flash_sig("fwd", Tq, Tk, D, q.dtype),
            _flash_candidates("fwd", Tq, Tk, D, q.dtype.itemsize),
            timed(fwd), default=DEFAULT_FLASH_BLOCKS["fwd"], force=force)
    if "bwd" in kinds:
        out, lse = pk.fused_attention_fwd(q, k, v, causal=causal)
        do = jnp.ones_like(out)

        def bwd(bq, bk):
            return pk.fused_attention_bwd(q, k, v, out, lse, do,
                                          causal=causal, block_q=bq,
                                          block_k=bk)
        results["bwd"] = autotune(
            "flash_bwd", _flash_sig("bwd", Tq, Tk, D, q.dtype),
            _flash_candidates("bwd", Tq, Tk, D, q.dtype.itemsize),
            timed(bwd), default=DEFAULT_FLASH_BLOCKS["bwd"], force=force)
    return results


# ---------------------------------------------------------------------------
# paged decode attention backend
# ---------------------------------------------------------------------------
#
# The decode kernel's block size IS the KV page (one physical page per
# sequential grid step), so the tunable is which FORMULATION wins for a
# given decode geometry: the Pallas paged walk (HBM traffic ∝ cached
# tokens; TPU) or the XLA gather+softmax (what GSPMD can shard; wins on
# CPU and for tiny pools where gather overhead is noise).

def _decode_sig(S: int, H: int, D: int, page: int, dtype) -> Tuple:
    return (int(S), int(H), int(D), int(page), str(dtype))


def decode_backend(S: int, H: int, D: int, page: int,
                   dtype: str = "") -> str:
    """``"pallas"`` or ``"xla"`` for this decode-attention geometry:
    the cache's measured winner, else pallas on TPU / XLA elsewhere.
    Read-only — called from the kernel wrapper at trace time."""
    hit = lookup("decode_attn", _decode_sig(S, H, D, page, dtype))
    if hit is not None:
        return str(hit["config"])
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:
        platform = "cpu"
    return "pallas" if platform == "tpu" else "xla"


def tune_decode(q, k_pages, v_pages, page_table, seq_lens,
                iters: int = 20, force: bool = False) -> str:
    """Measure both decode-attention formulations on these exact
    operands and persist the winner (keyed by slots × heads × head_dim ×
    page × dtype).  Candidate compilation goes through the persistent
    executable cache (``lower=`` write-through), so a re-tune on a
    relaunched host compiles nothing it already built.  Returns the
    winning backend name."""
    import jax
    from . import pallas_kernels as pk
    S, H, D = q.shape
    page = k_pages.shape[2]

    def build(backend):
        return jax.jit(functools.partial(
            pk.decode_attention, use_pallas=(backend == "pallas")))

    def lower(backend):
        return build(backend).lower(q, k_pages, v_pages, page_table,
                                    seq_lens)

    def measure(backend, compiled=None):
        from .. import telemetry as _tel
        fn = compiled if compiled is not None else build(backend)
        out = fn(q, k_pages, v_pages, page_table, seq_lens)
        jax.block_until_ready(out)
        with _tel.span("autotune/measure", cat="autotune",
                       timed=True) as sp:
            for _ in range(iters):
                out = fn(q, k_pages, v_pages, page_table, seq_lens)
            jax.block_until_ready(out)
        return sp.duration / iters

    winner = autotune(
        "decode_attn", _decode_sig(S, H, D, page, q.dtype),
        ["xla", "pallas"], measure,
        default=decode_backend(S, H, D, page, str(q.dtype)),
        force=force, lower=lower)
    return str(winner)
