"""Pallas TPU kernels for the local-shard half of the sharded embedding
plane: row gather (lookup) and row scatter (touched-rows update).

Why kernels at all: the shard-local step of a routed embedding lookup is
a batch of *random* single-row DMAs against a table that lives in HBM —
the access pattern XLA's generic ``gather``/``scatter`` lowering handles
with materialized index arithmetic, while a Pallas kernel with scalar-
prefetched ids turns each grid step into exactly one (1, D) row DMA
(``PrefetchScalarGridSpec``: the index map reads the id *before* the
block fetch, so the DMA goes straight to the right row — the same
mechanism jax's own TPU embedding kernels use).  The 2-bit quantization
kernel in :mod:`mxnet_tpu.ops.pallas_kernels` is the in-repo template
for the streaming structure; this module adds the data-dependent block
index.

Backend selection follows the autotuner discipline (ops/autotune.py,
the TVM measure-and-cache pattern): ``MXNET_TPU_PALLAS_EMBED=1`` forces
the Pallas path, ``=0`` forces the XLA ``take``/``segment_sum``
fallback, and unset ("auto") consults the persisted autotune cache —
:func:`tune_embedding` measures both backends on the real device and
records the winner under ``embed_gather`` / ``embed_scatter`` keys, so
the knob *defaults to the measured winner* per (rows, dim, n) shape.
Off-TPU both kernels run through the Pallas interpreter, so the same
code path is tested on CPU (where XLA wins and the tuner says so).

Contracts (both backends):

* :func:`embedding_gather` — ``ids`` must be in-range ``[0, rows)``
  (callers clip and mask; the routing layer in
  :mod:`mxnet_tpu.sparse.embedding` does exactly that).
* :func:`embedding_scatter` — ``ids`` must be SORTED ascending; entries
  with ``ids >= rows`` are dropped (the XLA path via ``mode="drop"``,
  the Pallas path by clipping into the last row with a no-op payload —
  callers pass zero rows in ``add`` mode / current rows in ``set``
  mode for padding entries).  ``mode="add"`` accumulates duplicate ids
  (sorted, so same-row visits are consecutive and the VMEM block
  carries); ``mode="set"`` is first-wins (callers dedup first — the
  update path always does, via its owner-side ``segment_sum``).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.pallas_kernels import _interpret, _out_struct

__all__ = ["embedding_gather", "embedding_scatter", "embed_backend",
           "tune_embedding", "gather_sig", "scatter_sig"]


def _knob() -> str:  # tpulint: disable=SL103
    # the backend choice is a STATIC property of the compiled program
    # (like a jit static arg): reading the env at trace time and baking
    # the winner in is the intended semantics, same as flash_blocks
    v = os.environ.get("MXNET_TPU_PALLAS_EMBED", "").strip().lower()
    if v in ("1", "pallas", "on", "true"):
        return "pallas"
    if v in ("0", "xla", "off", "false"):
        return "xla"
    return "auto"


def gather_sig(rows: int, dim: int, n: int, dtype) -> tuple:
    return (int(rows), int(dim), int(n), str(dtype))


scatter_sig = gather_sig


def embed_backend(kind: str, rows: int, dim: int, n: int,
                  dtype="float32") -> str:
    """Resolve the backend for one kernel call: the env knob wins; "auto"
    reads the persisted autotune cache (the :func:`tune_embedding` write
    side) and falls back to "xla" — the measured default on every rig
    where nobody has tuned (XLA wins on CPU interpret mode by orders of
    magnitude; on TPU the tuner decides).  Pure cache read — safe at
    trace time, like ``flash_blocks``."""
    k = _knob()
    if k != "auto":
        return k
    from ..ops import autotune as _autotune
    hit = _autotune.lookup("embed_%s" % ("gather" if kind == "gather"
                                         else "scatter"),
                           gather_sig(rows, dim, n, dtype))
    if hit is not None and hit.get("config") in ("pallas", "xla"):
        return hit["config"]
    return "xla"


# ---------------------------------------------------------------------------
# gather
# ---------------------------------------------------------------------------

def _gather_kernel(ids_ref, t_ref, o_ref):
    o_ref[:] = t_ref[:]


def _gather_pallas(table, ids, interpret):
    n = ids.shape[0]
    dim = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        # the id is read from SMEM before the block fetch: one (1, D)
        # row DMA per grid step, straight from the table's HBM row
        in_specs=[pl.BlockSpec((1, dim), lambda i, ids_ref: (ids_ref[i], 0))],
        out_specs=pl.BlockSpec((1, dim), lambda i, ids_ref: (i, 0)))
    with jax.enable_x64(False):
        return pl.pallas_call(
            _gather_kernel, grid_spec=grid_spec,
            out_shape=_out_struct((n, dim), table.dtype, ids, table),
            interpret=interpret, name="embedding_gather",
        )(ids.astype(jnp.int32), table)


def embedding_gather(table, ids, backend=None):
    """``table[ids]`` — (rows, D) x (n,) -> (n, D).  ``ids`` int32,
    in-range.  ``backend``: "pallas" | "xla" | None (resolve via
    :func:`embed_backend`)."""
    rows, dim = table.shape
    n = ids.shape[0]
    if backend is None:
        backend = embed_backend("gather", rows, dim, n, table.dtype)
    if backend == "pallas":
        return _gather_pallas(table, ids, _interpret(table))
    return jnp.take(table, ids.astype(jnp.int32), axis=0)


# ---------------------------------------------------------------------------
# scatter (add / set)
# ---------------------------------------------------------------------------

def _scatter_kernel(ids_ref, r_ref, t_ref, o_ref, *, add):
    i = pl.program_id(0)
    # sorted ids: a revisit of the SAME table row is always the previous
    # grid step, so the o_ref block carries in VMEM and we accumulate
    # (add) or keep the first write (set) instead of re-initializing
    prev_same = jax.lax.cond(
        i == 0, lambda: False,
        lambda: ids_ref[i] == ids_ref[jnp.maximum(i - 1, 0)])

    @pl.when(jnp.logical_not(prev_same))
    def _first():
        o_ref[:] = t_ref[:] + r_ref[:] if add else r_ref[:]

    if add:
        @pl.when(prev_same)
        def _again():
            o_ref[:] = o_ref[:] + r_ref[:]


def _scatter_pallas(table, ids, rows, add, interpret):
    n = ids.shape[0]
    dim = table.shape[1]
    nrows = table.shape[0]
    ids32 = jnp.clip(ids.astype(jnp.int32), 0, nrows - 1)
    kern = functools.partial(_scatter_kernel, add=add)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, dim), lambda i, ids_ref: (i, 0)),
            pl.BlockSpec((1, dim), lambda i, ids_ref: (ids_ref[i], 0)),
        ],
        out_specs=pl.BlockSpec((1, dim), lambda i, ids_ref: (ids_ref[i], 0)))
    with jax.enable_x64(False):
        return pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=_out_struct((nrows, dim), table.dtype, ids, rows,
                                  table),
            # the table IS the output: untouched rows never DMA, touched
            # rows read-modify-write in place (operand index counts the
            # scalar-prefetch arg: ids=0, rows=1, table=2)
            input_output_aliases={2: 0},
            interpret=interpret, name="embedding_scatter",
        )(ids32, rows.astype(table.dtype), table)


def embedding_scatter(table, ids, rows, mode: str = "add", backend=None):
    """Scatter ``rows`` into ``table`` at ``ids`` (sorted ascending);
    returns the new table.  ``mode="add"`` accumulates duplicates,
    ``mode="set"`` writes first-wins.  Entries with ``ids >= rows(table)``
    are dropped (XLA) / must carry a no-op payload (Pallas — zero rows in
    add mode, the current row value in set mode); the routing layer
    guarantees both."""
    if mode not in ("add", "set"):
        raise ValueError("embedding_scatter mode must be add|set, got %r"
                         % (mode,))
    nrows, dim = table.shape
    n = ids.shape[0]
    if backend is None:
        backend = embed_backend("scatter", nrows, dim, n, table.dtype)
    if backend == "pallas":
        return _scatter_pallas(table, ids, rows, mode == "add",
                               _interpret(table))
    ids32 = ids.astype(jnp.int32)
    rows = rows.astype(table.dtype)
    if mode == "add":
        return table.at[ids32].add(rows, mode="drop")
    # no unique_indices promise: the routed update pads with duplicate
    # out-of-range ids (dropped, but the guarantee would still be false)
    return table.at[ids32].set(rows, mode="drop")


# ---------------------------------------------------------------------------
# autotune write side
# ---------------------------------------------------------------------------

def tune_embedding(rows: int, dim: int, n: int, dtype="float32",
                   iters: int = 10, force: bool = False) -> dict:
    """Measure gather + scatter on the current device for this shape and
    persist the winning backend in the autotune cache (the read side is
    :func:`embed_backend`).  Measurement gates on ``MXNET_TPU_AUTOTUNE=1``
    unless ``force``; returns ``{"gather": backend, "scatter": backend}``.
    """
    import numpy as np
    from ..ops import autotune as _autotune
    rs = np.random.RandomState(0)
    table = jnp.asarray(rs.rand(rows, dim).astype(dtype))
    ids = jnp.asarray(np.sort(rs.randint(0, rows, n)).astype(np.int32))
    grows = jnp.asarray(rs.rand(n, dim).astype(dtype))

    def timed(fn):
        def run(cand):
            from .. import telemetry as _tel
            out = fn(cand)
            jax.block_until_ready(out)       # warm (compile excluded)
            with _tel.span("autotune/measure", cat="autotune",
                           timed=True) as sp:
                for _ in range(iters):
                    out = fn(cand)
                jax.block_until_ready(out)
            return sp.duration / iters
        return run

    g_jit = jax.jit(embedding_gather, static_argnames=("backend",))
    s_jit = jax.jit(embedding_scatter, static_argnames=("mode", "backend"))
    out = {}
    out["gather"] = _autotune.autotune(
        "embed_gather", gather_sig(rows, dim, n, dtype), ("xla", "pallas"),
        timed(lambda b: g_jit(table, ids, backend=b)),
        default="xla", force=force)
    out["scatter"] = _autotune.autotune(
        "embed_scatter", scatter_sig(rows, dim, n, dtype), ("xla", "pallas"),
        timed(lambda b: s_jit(table, ids, grows, mode="add", backend=b)),
        default="xla", force=force)
    return out
