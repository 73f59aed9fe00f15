"""Mesh-sharded embedding tables with touched-rows-only compute.

The in-jit sparse plane (ROADMAP item: "Sparse at scale").  The host
boundary already speaks row_sparse (:mod:`mxnet_tpu.ndarray.sparse`:
retain / merge / kvstore ``row_sparse_pull`` / lazy optimizer updates) —
but inside a compiled program every table was dense and replicated, so
an embedding had to fit one device's HBM and a gradient step moved
table-sized bytes.  This module moves the row_sparse *discipline* inside
jit:

* the table is **row-sharded** over one mesh axis (``ep`` when active,
  else ``dp`` — the ``__shard__``/placement grammar's ``P(axis)`` on dim
  0, :mod:`mxnet_tpu.parallel.placement`), so per-device residency is
  ``table/S``;
* a lookup is compiled as **owner-shard routing**: dedup the local ids
  (in-jit ``unique``), bucket them by owner shard, ``all_to_all`` the id
  lists, gather locally at shard shapes
  (:mod:`mxnet_tpu.sparse.kernels` — Pallas or XLA), and ``all_to_all``
  the rows back.  Per-step collective payload is
  ``S x C x (4 + 4D)`` bytes per device — a function of **touched rows
  and dim only, never table size** (:func:`lookup_wire_bytes` is the
  analytic model the dryrun audit holds measurements against, via the
  per-axis collective accounting in :mod:`mxnet_tpu.parallel.audit`);
* the gradient path dedups ids + ``segment_sum``s duplicate
  contributions in-jit, routes the ``(ids, rows)`` pairs to their owner
  shards, and the sharded **lazy update**
  (:meth:`ShardedEmbedding.apply_sgd` / :meth:`~ShardedEmbedding.
  apply_adam`) touches ONLY those rows of the table and its optimizer
  slots, at shard shapes — the same semantics as the host
  ``sgd_row_sparse_update`` / ``adam_row_sparse_update`` reference
  (``optimizer.py`` lazy paths), proven equal in
  ``tests/test_sparse_plane.py``.

Capacity: routing uses a fixed per-destination bucket of ``C`` slots
(static shapes — the MoE dispatch discipline, :mod:`mxnet_tpu.parallel.
moe`).  The default ``C = local_batch`` can never drop an id (each
sender holds at most ``local_batch`` distinct ids); a smaller
``capacity_factor`` shrinks wire bytes when the id distribution is
known, and :meth:`ShardedEmbedding.lookup` with ``stats=True`` reports
per-shard received counts and drops so load drills can assert the
routing stays bounded (dedup means a hot row costs each shard at most
one slot per *sender*, not one per occurrence).

Knobs: ``MXNET_TPU_PALLAS_EMBED`` (kernels backend — 1/0/auto, see
:mod:`.kernels`); docs/sparse.md has the full table and the audit
how-to.
"""
from __future__ import annotations

import weakref
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from . import kernels as _kernels

__all__ = ["ShardedEmbedding", "lookup_wire_bytes",
           "step_alltoall_model_bytes", "live_tables"]

# live ShardedEmbedding registry (weak): GC306 reads table sizes from it
# so the "you densified your embedding grad" check can compare collective
# payloads against the tables actually in play
_REGISTRY: "weakref.WeakValueDictionary[int, ShardedEmbedding]" = \
    weakref.WeakValueDictionary()
_REG_SEQ = [0]


def live_tables():
    """[(name, global_table_bytes)] for every live ShardedEmbedding."""
    out = []
    for emb in list(_REGISTRY.values()):
        out.append((emb.name, emb.table_bytes))
    return out


def lookup_wire_bytes(n_ids_global: int, dim: int, num_shards: int,
                      capacity: Optional[int] = None,
                      itemsize: int = 4) -> Dict[str, int]:
    """Analytic per-device all-to-all payload of ONE routed lookup:
    ``{"ids": S*C*4, "rows": S*C*dim*itemsize}`` — the quantity the
    dryrun audit compares against measured HLO payloads.  Note what is
    absent: the table's row count."""
    S = max(1, int(num_shards))
    b = int(n_ids_global) // S
    C = int(capacity) if capacity else b
    return {"ids": S * C * 4, "rows": S * C * int(dim) * int(itemsize)}


def step_alltoall_model_bytes(n_ids_global: int, dim: int, num_shards: int,
                              capacity: Optional[int] = None,
                              itemsize: int = 4) -> int:
    """Analytic per-device all-to-all bytes of one full training step on
    one table: the ids once and the rows twice —
    ``S*C*4 + 2*S*C*D*itemsize``.  The lookup and the update route the
    same ids by the same plan, so inside one compiled step the compiler
    keeps a single ids exchange; the rows go out to the lookup and their
    gradients come back to the update."""
    w = lookup_wire_bytes(n_ids_global, dim, num_shards, capacity, itemsize)
    return w["ids"] + 2 * w["rows"]


# ---------------------------------------------------------------------------
# routing plan (shard-local, in-jit)
# ---------------------------------------------------------------------------

def _plan(ids, S: int, rows_per: int, C: int, vpad: int):
    """Owner-shard routing plan for one device's ids: dedup, compute
    each unique id's owner shard and slot in that owner's bucket.

    Returns ``(uniq, inv, owner, pos, ok, dropped)``: ``uniq`` sorted
    unique ids padded with ``vpad`` (= S*rows_per, so pad entries get
    owner S — out of range, dropped by every ``mode="drop"`` scatter and
    never consuming real bucket capacity); ``inv`` maps original
    positions onto uniq; ``ok`` marks entries that fit their bucket;
    ``dropped`` counts real ids that overflowed capacity ``C``."""
    b = ids.shape[0]
    ids = ids.reshape(-1).astype(jnp.int32)
    uniq, inv = jnp.unique(ids, size=b, fill_value=vpad,
                           return_inverse=True)
    uniq = uniq.astype(jnp.int32)
    inv = inv.reshape(-1).astype(jnp.int32)
    owner = uniq // jnp.int32(rows_per)                 # pads -> S
    # uniq is sorted, so owner is sorted: position-in-bucket is the
    # offset from the first element of the owner's run
    first = jnp.searchsorted(owner, owner).astype(jnp.int32)
    pos = jnp.arange(b, dtype=jnp.int32) - first
    valid = uniq < jnp.int32(vpad)
    ok = valid & (pos < C)
    dropped = jnp.sum(valid & (pos >= C)).astype(jnp.int32)
    return uniq, inv, owner, pos, ok, dropped


# Every shard_map below passes check_vma=False: the shard-local kernels may
# be Pallas, and off-TPU the Pallas interpreter's own grid loop fails the
# varying-axes check (jax 0.9.0: "dynamic_slice requires varying manual
# axes to match").

def _a2a(x, axis: str, S: int):
    if S == 1:
        return x
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)


class ShardedEmbedding:
    """One row-sharded embedding table over a named mesh axis.

    Functional state: the table (and optimizer slots) are plain jax
    arrays the caller threads through :meth:`lookup` /
    :meth:`apply_sgd` / :meth:`apply_adam` — jit-friendly, donation-
    friendly, checkpointable (``resilience.checkpoint.save_embedding``).
    ``num_rows`` is padded up to a multiple of the shard count; padded
    rows are never looked up and never touched by updates, and
    :meth:`state_dict` strips them, so a 4-shard snapshot restores onto
    a 3-shard mesh (the elastic resize path) with nothing but a re-pad.
    """

    def __init__(self, num_rows: int, dim: int, mesh, axis: Optional[str]
                 = None, dtype=jnp.float32, capacity_factor: Optional[float]
                 = None, backend: Optional[str] = None,
                 name: str = "embedding"):
        from ..parallel.placement import as_mesh
        spec = mesh if hasattr(mesh, "mesh") else None
        self.mesh = as_mesh(mesh)
        if axis is None:
            if spec is not None:
                ep = getattr(spec, "ep_axis", None)
                if ep and spec.axis_size(ep) > 1:
                    axis = ep
                else:
                    axis = getattr(spec, "dp_axis", None) \
                        or self.mesh.axis_names[0]
            else:
                axis = self.mesh.axis_names[0]
        if axis not in self.mesh.axis_names:
            raise ValueError("embedding axis %r not in mesh axes %r"
                             % (axis, tuple(self.mesh.axis_names)))
        self.axis = axis
        self.num_shards = int(self.mesh.shape[axis])
        self.num_rows = int(num_rows)
        self.dim = int(dim)
        self.dtype = jnp.dtype(dtype)
        S = self.num_shards
        self.rows_per_shard = -(-self.num_rows // S)
        self.padded_rows = self.rows_per_shard * S
        self.sharding = NamedSharding(self.mesh, P(axis))
        self.capacity_factor = capacity_factor
        self.backend = backend
        self.name = name
        # jitted-program cache: one routed lookup/update program per
        # (kind, capacity, hyperparams) — without it every call builds a
        # fresh shard_map closure and pays a full XLA compile (tens of
        # seconds per *update* on a contended multi-process rig)
        self._programs: Dict[tuple, object] = {}
        _REG_SEQ[0] += 1
        _REGISTRY[_REG_SEQ[0]] = self

    # -- sizing ----------------------------------------------------------
    @property
    def table_bytes(self) -> int:
        return self.padded_rows * self.dim * self.dtype.itemsize

    def capacity(self, n_ids_global: int) -> int:
        """Per-destination bucket slots for a batch of ``n_ids_global``
        ids: ``local_batch`` (never drops) unless a ``capacity_factor``
        shrinks it (``ceil(local*factor/S)``, the MoE formula)."""
        b = n_ids_global // self.num_shards
        if self.capacity_factor is None:
            return max(1, b)
        import math
        return max(1, math.ceil(b * self.capacity_factor /
                                self.num_shards))

    def wire_model(self, n_ids_global: int) -> Dict[str, int]:
        return lookup_wire_bytes(n_ids_global, self.dim, self.num_shards,
                                 self.capacity(n_ids_global),
                                 self.dtype.itemsize)

    # -- state -----------------------------------------------------------
    def init_state(self, seed: int = 0, scale: float = 0.01):
        """The table, row-sharded on the mesh (each shard initialized on
        its owner — the full table is never materialized on one device),
        tagged ``embedding`` on the memory plane."""
        @jax.jit
        def init(key):
            t = scale * jax.random.normal(
                key, (self.padded_rows, self.dim), jnp.float32)
            return t.astype(self.dtype)
        with self.mesh:
            table = jax.jit(init, out_shardings=self.sharding)(
                jax.random.PRNGKey(seed))
        from ..telemetry import memory as _memory
        _memory.tag(table, "embedding", label=self.name)
        return table

    def zeros_slot(self, dtype=jnp.float32):
        """One optimizer slot (momentum / Adam mean / var), sharded like
        the table."""
        with self.mesh:
            slot = jax.jit(
                lambda: jnp.zeros((self.padded_rows, self.dim), dtype),
                out_shardings=self.sharding)()
        from ..telemetry import memory as _memory
        _memory.tag(slot, "embedding", label=self.name + ".slot")
        return slot

    # -- lookup ----------------------------------------------------------
    def _lookup_local(self, C: int, with_stats: bool):
        S, rows_per = self.num_shards, self.rows_per_shard
        axis, vpad = self.axis, self.padded_rows
        backend = self.backend
        dim = self.dim

        def fn(table_l, ids_l):
            uniq, inv, owner, pos, ok, dropped = _plan(
                ids_l, S, rows_per, C, vpad)
            send = jnp.full((S, C), vpad, jnp.int32) \
                .at[owner, pos].set(uniq, mode="drop")
            recv = _a2a(send, axis, S)                   # ids asked of me
            my = lax.axis_index(axis).astype(jnp.int32) if S > 1 \
                else jnp.int32(0)
            local = recv - my * jnp.int32(rows_per)
            in_range = (local >= 0) & (local < rows_per)
            lidx = jnp.clip(local, 0, rows_per - 1).reshape(-1)
            rows = _kernels.embedding_gather(table_l, lidx,
                                             backend=backend)
            rows = jnp.where(in_range.reshape(-1, 1), rows,
                             jnp.zeros((), rows.dtype))
            back = _a2a(rows.reshape(S, C, dim), axis, S)
            got = back[jnp.clip(owner, 0, S - 1),
                       jnp.clip(pos, 0, C - 1)]
            got = jnp.where(ok[:, None], got, jnp.zeros((), got.dtype))
            out = jnp.take(got, inv, axis=0)
            if not with_stats:
                return out
            received = jnp.sum(in_range).astype(jnp.int32).reshape(1)
            return out, received, dropped.reshape(1)
        return fn

    def lookup(self, table, ids, stats: bool = False):
        """Routed lookup: ``ids`` (B,) int — B divisible by the shard
        count, sharded over the table's axis (a dp-sharded batch already
        is, when the table rides dp).  Returns (B, dim) rows; ids beyond
        a bucket's capacity return zero rows (impossible at the default
        capacity).  ``stats=True`` additionally returns
        ``(received_per_shard (S,), dropped_per_shard (S,))`` for load
        drills."""
        B = int(ids.shape[0])
        if B % self.num_shards:
            raise ValueError(
                "lookup batch %d is not divisible by the %r shard count "
                "%d" % (B, self.axis, self.num_shards))
        C = self.capacity(B)
        axis = self.axis
        key = ("lookup", C, bool(stats))
        mapped = self._programs.get(key)
        if mapped is None:
            fn = self._lookup_local(C, stats)
            out_specs = (P(axis), P(axis), P(axis)) if stats else P(axis)
            mapped = jax.jit(shard_map(fn, mesh=self.mesh,
                                       in_specs=(P(axis), P(axis)),
                                       out_specs=out_specs,
                                       check_vma=False))
            self._programs[key] = mapped
        from .. import telemetry as _tel
        from ..parallel.audit import record_collective
        from ..resilience import watchdog as _wd
        w = self.wire_model(B)
        # the id/row all_to_all pair is a collective entry point: span +
        # watchdog deadline + audit-trail record, the moe_ffn discipline
        with _tel.span("collective/embedding_lookup", cat="collective",
                       metric="parallel.collective_seconds",
                       kind="all-to-all", bytes=w["ids"] + w["rows"]), \
                _wd.watch("sparse.%s.lookup" % self.name,
                          kind="collective"):
            with self.mesh:
                res = mapped(table, ids)
        record_collective("all-to-all", "%s.lookup id+row routing"
                          % self.name, bytes=w["ids"] + w["rows"])
        return res

    # -- sparse gradient + lazy updates ----------------------------------
    def _update_local(self, C: int, kind: str, hyper: dict):
        S, rows_per = self.num_shards, self.rows_per_shard
        axis, vpad = self.axis, self.padded_rows
        backend = self.backend
        dim = self.dim
        # hyperparameters stay PYTHON floats so every derived scalar
        # ((1 - beta1), -clip, ...) is computed in double and rounds to
        # f32 at the same point the host lazy kernels round.  Parity
        # with the eager host kernels: this program compiles FUSED, and
        # XLA:CPU FMA-contracts `a*b + c` (single rounding) — so the
        # bit-parity contract holds exactly when every product in the
        # chain is exact (power-of-two lr/momentum/wd/rescale, few-
        # mantissa-bit betas; tests/test_sparse_plane.py pins those),
        # and to f32 roundoff (~1 ulp) for arbitrary hyperparameters.
        # The installed XLA also folds `+ wd * w` into the cross-sender
        # scatter-add of `route`, which then rounds once per sender:
        # the tests hold parity to that bound, not bit for bit.
        lr = float(hyper["lr"])
        wd = float(hyper.get("wd", 0.0))
        rescale = float(hyper.get("rescale_grad", 1.0))
        clip = hyper.get("clip_gradient")
        mom = float(hyper.get("momentum", 0.0))
        beta1 = float(hyper.get("beta1", 0.9))
        beta2 = float(hyper.get("beta2", 0.999))
        eps = float(hyper.get("epsilon", 1e-8))

        def route(ids_l, grows_l):
            """(ids, grad rows) -> this shard's touched rows: sorted
            unique LOCAL row ids (pads = rows_per) + f32 summed grads."""
            uniq, inv, owner, pos, ok, _dropped = _plan(
                ids_l, S, rows_per, C, vpad)
            # in-jit dedup: duplicate ids' contributions segment-sum
            # into one row per unique id BEFORE anything moves
            g_uniq = jax.ops.segment_sum(
                grows_l.astype(jnp.float32), inv,
                num_segments=ids_l.shape[0])
            send_ids = jnp.full((S, C), vpad, jnp.int32) \
                .at[owner, pos].set(uniq, mode="drop")
            send_rows = jnp.zeros((S, C, dim), jnp.float32) \
                .at[owner, pos].set(g_uniq, mode="drop")
            recv_ids = _a2a(send_ids, axis, S)
            recv_rows = _a2a(send_rows, axis, S)
            my = lax.axis_index(axis).astype(jnp.int32) if S > 1 \
                else jnp.int32(0)
            local = recv_ids - my * jnp.int32(rows_per)
            in_range = (local >= 0) & (local < rows_per)
            lids = jnp.where(in_range, local, rows_per).reshape(-1)
            # cross-sender dedup at the owner: the same row can arrive
            # from several senders; one segment_sum folds them
            u2, inv2 = jnp.unique(lids, size=S * C, fill_value=rows_per,
                                  return_inverse=True)
            u2 = u2.astype(jnp.int32)
            inv2 = inv2.reshape(-1).astype(jnp.int32)
            g2 = jax.ops.segment_sum(recv_rows.reshape(S * C, dim), inv2,
                                     num_segments=S * C)
            ok2 = u2 < rows_per
            return u2, g2, ok2

        def prep_grad(g2, w_rows):
            """The host lazy-SGD/Adam gradient prologue, bit-for-bit
            (ndarray/sparse.py): SGD clips BEFORE weight decay, Adam
            after."""
            g = g2 * rescale
            if kind == "sgd":
                if clip is not None and clip > 0:
                    g = jnp.clip(g, -clip, clip)
                g = g + wd * w_rows
            else:
                g = g + wd * w_rows
                if clip is not None and clip > 0:
                    g = jnp.clip(g, -clip, clip)
            return g

        def scatter_set(buf, u2, ok2, new_rows, cur_rows):
            # pads/out-of-range write their CURRENT value (a no-op) on
            # backends that cannot drop (Pallas); real rows write the
            # update.  u2 sorted => the kernel's sorted-ids contract.
            vals = jnp.where(ok2[:, None], new_rows, cur_rows)
            return _kernels.embedding_scatter(buf, u2, vals, mode="set",
                                              backend=backend)

        def sgd_fn(table_l, mom_l, ids_l, grows_l):
            u2, g2, ok2 = route(ids_l, grows_l)
            idx = jnp.clip(u2, 0, rows_per - 1)
            w_rows = _kernels.embedding_gather(
                table_l, idx, backend=backend).astype(jnp.float32)
            g = prep_grad(g2, w_rows)
            if mom_l is None:
                new_w = w_rows - lr * g
                return scatter_set(table_l, u2, ok2,
                                   new_w.astype(table_l.dtype),
                                   w_rows.astype(table_l.dtype))
            m_rows = _kernels.embedding_gather(
                mom_l, idx, backend=backend).astype(jnp.float32)
            new_m = mom * m_rows - lr * g
            new_w = w_rows + new_m
            table_n = scatter_set(table_l, u2, ok2,
                                  new_w.astype(table_l.dtype),
                                  w_rows.astype(table_l.dtype))
            mom_n = scatter_set(mom_l, u2, ok2,
                                new_m.astype(mom_l.dtype),
                                m_rows.astype(mom_l.dtype))
            return table_n, mom_n

        def adam_fn(table_l, mean_l, var_l, ids_l, grows_l):
            u2, g2, ok2 = route(ids_l, grows_l)
            idx = jnp.clip(u2, 0, rows_per - 1)
            w_rows = _kernels.embedding_gather(
                table_l, idx, backend=backend).astype(jnp.float32)
            g = prep_grad(g2, w_rows)
            m_rows = beta1 * _kernels.embedding_gather(
                mean_l, idx, backend=backend) + (1 - beta1) * g
            v_rows = beta2 * _kernels.embedding_gather(
                var_l, idx, backend=backend) + (1 - beta2) * g * g
            new_w = w_rows - lr * m_rows / (jnp.sqrt(v_rows) + eps)
            table_n = scatter_set(table_l, u2, ok2,
                                  new_w.astype(table_l.dtype),
                                  w_rows.astype(table_l.dtype))
            mean_n = scatter_set(
                mean_l, u2, ok2, m_rows,
                _kernels.embedding_gather(mean_l, idx, backend=backend))
            var_n = scatter_set(
                var_l, u2, ok2, v_rows,
                _kernels.embedding_gather(var_l, idx, backend=backend))
            return table_n, mean_n, var_n

        return sgd_fn if kind == "sgd" else adam_fn

    def _check_update_batch(self, ids):
        B = int(ids.shape[0])
        if B % self.num_shards:
            raise ValueError(
                "update batch %d is not divisible by the %r shard count "
                "%d" % (B, self.axis, self.num_shards))
        return self.capacity(B)

    def apply_sgd(self, table, mom, ids, grad_rows, lr, momentum=0.0,
                  wd=0.0, rescale_grad=1.0, clip_gradient=None):
        """Sharded lazy SGD: update ONLY the rows named by ``ids`` (B,),
        with duplicate contributions summed — the in-jit twin of the
        host ``sgd_row_sparse_update`` (``ndarray/sparse.py``), at shard
        shapes.  ``grad_rows`` (B, dim) pairs with ``ids``; ``mom`` may
        be None (momentum-free).  Returns ``(table, mom)``."""
        from .. import telemetry as _tel
        from ..resilience import watchdog as _wd
        C = self._check_update_batch(ids)
        wbytes = sum(self.wire_model(int(ids.shape[0])).values())
        hyper = dict(lr=lr, momentum=momentum, wd=wd,
                     rescale_grad=rescale_grad, clip_gradient=clip_gradient)
        axis = self.axis
        key = ("sgd", C, mom is None, tuple(sorted(hyper.items())))
        mapped = self._programs.get(key)
        if mom is None:
            if mapped is None:
                base = self._update_local(C, "sgd", hyper)
                fn = lambda t, i, g: base(t, None, i, g)   # noqa: E731
                mapped = jax.jit(shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(P(axis), P(axis), P(axis)),
                    out_specs=P(axis), check_vma=False))
                self._programs[key] = mapped
            with _tel.span("collective/embedding_update",
                           cat="collective",
                           metric="parallel.collective_seconds",
                           kind="all-to-all", bytes=wbytes), \
                    _wd.watch("sparse.%s.lazy_update" % self.name,
                              kind="collective"), self.mesh:
                out = (mapped(table, ids, grad_rows), None)
        else:
            if mapped is None:
                fn = self._update_local(C, "sgd", hyper)
                mapped = jax.jit(shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(P(axis), P(axis), P(axis), P(axis)),
                    out_specs=(P(axis), P(axis)), check_vma=False))
                self._programs[key] = mapped
            with _tel.span("collective/embedding_update",
                           cat="collective",
                           metric="parallel.collective_seconds",
                           kind="all-to-all", bytes=wbytes), \
                    _wd.watch("sparse.%s.lazy_update" % self.name,
                              kind="collective"), self.mesh:
                out = mapped(table, mom, ids, grad_rows)
        self._note_update(int(ids.shape[0]))
        return out

    def apply_adam(self, table, mean, var, ids, grad_rows, lr, beta1=0.9,
                   beta2=0.999, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                   clip_gradient=None):
        """Sharded lazy Adam over touched rows only (the in-jit twin of
        the host ``adam_row_sparse_update``).  Returns
        ``(table, mean, var)``."""
        from .. import telemetry as _tel
        from ..resilience import watchdog as _wd
        C = self._check_update_batch(ids)
        wbytes = sum(self.wire_model(int(ids.shape[0])).values())
        hyper = dict(lr=lr, beta1=beta1, beta2=beta2, epsilon=epsilon,
                     wd=wd, rescale_grad=rescale_grad,
                     clip_gradient=clip_gradient)
        axis = self.axis
        key = ("adam", C, tuple(sorted(hyper.items())))
        mapped = self._programs.get(key)
        if mapped is None:
            fn = self._update_local(C, "adam", hyper)
            mapped = jax.jit(shard_map(
                fn, mesh=self.mesh,
                in_specs=(P(axis),) * 5,
                out_specs=(P(axis), P(axis), P(axis)), check_vma=False))
            self._programs[key] = mapped
        with _tel.span("collective/embedding_update", cat="collective",
                       metric="parallel.collective_seconds",
                       kind="all-to-all", bytes=wbytes), \
                _wd.watch("sparse.%s.lazy_update" % self.name,
                          kind="collective"), self.mesh:
            out = mapped(table, mean, var, ids, grad_rows)
        self._note_update(int(ids.shape[0]))
        return out

    def _note_update(self, n_ids: int):
        from ..parallel.audit import record_collective
        w = self.wire_model(n_ids)
        record_collective("all-to-all", "%s.lazy_update grad routing"
                          % self.name, bytes=w["ids"] + w["rows"])

    # -- checkpoint / elastic resharding ---------------------------------
    def _to_host(self, arr) -> np.ndarray:
        """Host copy of one state array.  In a multi-process gang the
        shards live on other processes' devices, so the fetch is an
        all-gather (a jit identity to the replicated sharding) — a
        COLLECTIVE: every rank must call :meth:`state_dict` at the same
        point even if only the saver rank writes the file."""
        if isinstance(arr, np.ndarray) or getattr(
                arr, "is_fully_addressable", True):
            return np.asarray(arr)
        gather = self._programs.get(("gather_host",))
        if gather is None:
            gather = jax.jit(lambda x: x, out_shardings=NamedSharding(
                self.mesh, P()))
            self._programs[("gather_host",)] = gather
        with self.mesh:
            rep = gather(arr)
        return np.asarray(rep)

    def state_dict(self, table, **slots) -> Dict[str, np.ndarray]:
        """Host snapshot with shard padding STRIPPED — the world-size-
        independent form a resharding restore re-pads from."""
        out = {"table": self._to_host(table)[:self.num_rows]}
        for k, v in slots.items():
            if v is not None:
                out[k] = self._to_host(v)[:self.num_rows]
        return out

    def load_array(self, host_array) -> jax.Array:
        """Re-pad a (num_rows, dim) host array for THIS mesh's shard
        count and place it row-sharded — the resharding restore
        primitive (a 4-shard snapshot lands on a 3-shard mesh here)."""
        host = np.asarray(host_array)
        if host.shape[0] != self.num_rows:
            raise ValueError("embedding %r: snapshot has %d rows, table "
                             "has %d" % (self.name, host.shape[0],
                                         self.num_rows))
        pad = self.padded_rows - self.num_rows
        if pad:
            host = np.concatenate(
                [host, np.zeros((pad,) + host.shape[1:], host.dtype)])
        arr = jax.device_put(host, self.sharding)
        from ..telemetry import memory as _memory
        _memory.tag(arr, "embedding", label=self.name + ".restored")
        return arr

    def reshard(self, mesh, axis: Optional[str] = None) -> "ShardedEmbedding":
        """A sibling plane over a different mesh (the elastic
        ``reform_mesh`` path): same rows/dim/name, new shard count; move
        state across with ``state_dict`` + ``load_array``."""
        return ShardedEmbedding(
            self.num_rows, self.dim, mesh,
            axis=axis if axis is not None else self.axis,
            dtype=self.dtype, capacity_factor=self.capacity_factor,
            backend=self.backend, name=self.name)
