"""ShardedTrainer — the SPMD training engine.

This is the TPU-native replacement for the reference's entire data-parallel
machinery: DataParallelExecutorGroup's per-device executors + KVStore
reduce/broadcast (executor_group.py:129 + kvstore comm.h) collapse into ONE
jitted step function over a jax Mesh:

  params: replicated over 'dp' (or sharded over 'tp' when a tp axis exists)
  batch:  sharded over 'dp'
  step = forward → loss → grad (XLA inserts psum over dp) → optimizer update

The gradient all-reduce rides ICI as a single fused psum — the kvstore
'device'/'nccl' path taken to its limit.  Donated argnums make the update
in-place in HBM.  Works identically on a CPU device mesh (tests) and a TPU
pod slice (multi-host: same program, jax.distributed handles DCN).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..executor import GraphProgram
from . import placement as _placement
from .mesh import MeshSpec

__all__ = ["ShardedTrainer", "sgd_step_fn", "zero_enabled"]


def zero_enabled(shard_optimizer_state: bool, zero=None) -> bool:
    """Resolve the ZeRO sharded-weight-update knob.

    Precedence: explicit ``zero=`` ctor arg > ``MXNET_TPU_ZERO`` env
    ("1"/"0") > follow ``shard_optimizer_state`` — if you asked for
    dp-sharded optimizer state you get the sharded update too, because
    it is strictly better (identical numerics, 1/dp update FLOPs, the
    grad all-reduce becomes reduce-scatter + overlapped weight
    all-gather).  ``MXNET_TPU_ZERO=0`` reverts to storage-only sharding
    for A/B runs."""
    if zero is not None:
        return bool(zero)
    import os
    v = os.environ.get("MXNET_TPU_ZERO")
    if v is not None:
        return v.strip().lower() not in ("0", "off", "false", "")
    return bool(shard_optimizer_state)


def _relaid(x, fmt):
    """``x`` in the layout (and sharding) ``fmt``, checked: a re-lay that
    comes back in another layout would only surface steps later, as the
    compiled step refusing its own state."""
    y = jax.device_put(x, fmt)
    if y.format.layout != fmt.layout:
        raise RuntimeError(
            "re-laying %s%s for the AUTO-layout step gave layout %s, not "
            "the %s that was asked for" % (x.dtype, x.shape,
                                           y.format.layout, fmt.layout))
    return y


def _tree_sgd(params, grads, mom, lr, momentum, wd, rescale):
    new_params = []
    new_mom = []
    for p, g, m in zip(params, grads, mom):
        g = g.astype(jnp.float32) * rescale + wd * p
        m2 = momentum * m - lr * g
        new_params.append((p + m2).astype(p.dtype))
        new_mom.append(m2)
    return tuple(new_params), tuple(new_mom)


class ShardedTrainer:
    """One-program data-parallel trainer for a Symbol graph."""

    def __init__(self, symbol, spec: MeshSpec, data_names=("data",),
                 label_names=("softmax_label",), lr=0.01, momentum=0.9,
                 wd=0.0001, loss_scale=1.0, param_dtype=None,
                 shard_optimizer_state=False, dynamic_loss_scale=False,
                 loss_scale_growth_interval=2000, nonfinite_budget=None,
                 guard_nonfinite=True, grad_accum=1, zero=None):
        self.symbol = symbol
        self.spec = spec
        self.prog = GraphProgram(symbol)
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.input_names = self.data_names + self.label_names
        self.param_names = [n for n in self.prog.arg_names
                            if n not in self.input_names]
        self.param_idx = [self.prog.arg_names.index(n)
                          for n in self.param_names]
        self.input_idx = {n: self.prog.arg_names.index(n)
                          for n in self.input_names}
        self.lr = lr
        self.momentum = momentum
        self.wd = wd
        self.param_dtype = param_dtype
        # gradient accumulation: one optimizer update per `grad_accum`
        # micro-batches, all inside ONE jitted program (lax.scan over a
        # leading micro dim).  The elastic-training resize uses this to
        # keep the GLOBAL batch constant when the world size changes:
        # accum = global_batch / (world * micro_batch)
        # (resilience/elastic.py grad_accum_for).
        if int(grad_accum) < 1:
            raise ValueError("grad_accum must be >= 1, got %r" % grad_accum)
        self.grad_accum = int(grad_accum)
        self._step = None
        # AOT executable for the step when the persistent compile cache
        # (mxnet_tpu/compile) is armed: the first step either
        # deserializes a warm entry (result=hit — the elastic-resume
        # path) or compiles and writes through (result=miss); later
        # steps call it directly.  None = cache off, dispatch via jit.
        self._step_exec = None
        from ..executor import backward_mirror_policy
        self._built_remat = backward_mirror_policy()
        # tensor parallelism: the tp mesh axis (auto-detected) + per-var
        # __shard__ annotations from the Symbol graph
        tp = spec.tp_axis
        if tp is None and "tp" in spec.mesh.axis_names:
            tp = "tp"
        self.tp_axis = tp if (tp and spec.mesh.shape.get(tp, 1) > 1) else None
        from ..placement import shard_annotations
        self._shard_attrs, self._act_shard_attrs = shard_annotations(
            self.prog.nodes)
        self._param_shapes = None   # filled by init_state; step shardings
        # ZeRO-style sharded weight update (the BIGARRAY/server-side-
        # optimizer analog, kvstore_dist.h:156 + kvstore_dist_server.h:187,
        # SURVEY §5.8; "Automatic Cross-Replica Sharding of Weight Update
        # in Data-Parallel Training", arXiv 2004.13336): momentum shards
        # over 'dp' AND the update math operates on the shards — grads
        # are constrained to the state shardings, so GSPMD reduces each
        # replica's partial straight into the owned shard (reduce-scatter
        # on the wire; XLA:CPU spells it as an all-reduce whose only
        # consumers are partition-sliced — the form the TPU
        # ReduceScatterCreator pass folds), the optimizer update runs at
        # 1/dp FLOPs/bytes per chip, and the new weights all-gather back
        # to their parameter sharding, schedulable against the other
        # parameters' updates.
        self.zero = zero_enabled(shard_optimizer_state, zero)
        self.shard_optimizer_state = bool(shard_optimizer_state) or self.zero
        self.shard_weight_update = self.zero and spec.dp_size > 1
        # -- resilience (resilience/guards.py): the non-finite detector and
        # the loss-scale automaton live INSIDE the jitted step; the host
        # only tracks the consecutive-bad-step budget and chaos hooks.
        from ..resilience import guards as _guards
        self.init_loss_scale = float(loss_scale)
        self.dynamic_loss_scale = bool(dynamic_loss_scale)
        self.loss_scale_growth_interval = int(loss_scale_growth_interval)
        self.guard_nonfinite = bool(guard_nonfinite)
        self.nonfinite_budget = (_guards.default_budget()
                                 if nonfinite_budget is None
                                 else int(nonfinite_budget))
        self._guard_state = None     # (scale f32, good-streak i32) on device
        self._bad_streak = 0
        self._skipped_steps = 0
        self._step_count = 0
        self._last_ok = True
        # -- pre-flight (analysis/preflight.py): with MXNET_TPU_PREFLIGHT=1
        # the first step statically checks the traced program before any
        # device executes it; runs once per trainer.
        self._step_donated = True
        self._preflight_done = False
        # -- attribution (telemetry/perf.py): with MXNET_TPU_ATTRIBUTION=1
        # one roofline/MFU report per step program, written a few steps in
        # so the telemetry histograms carry real measurements.
        self._attribution_done = False

    # -- placement (parallel/placement.py is the single rule source) ------
    def param_sharding(self, name: str, shape) -> NamedSharding:
        """Placement for one parameter: explicit ``__shard__`` Symbol attr
        wins (any mesh axis; the ctx_group-style per-layer annotation
        pattern), else the default tp recipe, else replicated — see
        :func:`~mxnet_tpu.parallel.placement.param_sharding`."""
        return _placement.param_sharding(name, shape, self.spec.mesh,
                                         tp_axis=self.tp_axis,
                                         ann=self._shard_attrs.get(name))

    def mom_sharding(self, name: str, shape) -> NamedSharding:
        """Sharding for one optimizer-state tensor (and, with the ZeRO
        update, the grad/update view of its parameter): the param's
        sharding plus the dp axis over the largest free divisible dim
        (:func:`~mxnet_tpu.parallel.placement.state_sharding`)."""
        base = self.param_sharding(name, shape)
        if not self.shard_optimizer_state:
            return base
        return _placement.state_sharding(base, shape, self.spec.mesh,
                                         self.spec.dp_axis)

    def _param_shardings(self):
        if self._param_shapes is None:
            from ..executor import _resolve_structs
            _, known, _ = _resolve_structs(
                self.symbol, getattr(self, "_last_shapes", {}) or {})
            self._param_shapes = {n: tuple(known[n].shape)
                                  for n in self.param_names if n in known}
        return tuple(self.param_sharding(n, self._param_shapes.get(n, ()))
                     for n in self.param_names)

    def _mom_shardings(self):
        self._param_shardings()   # ensure shapes resolved
        return tuple(self.mom_sharding(n, self._param_shapes.get(n, ()))
                     for n in self.param_names)

    def _arm_mesh(self):
        """Publish this trainer's mesh as the thread's current mesh:
        activation ``__shard__`` constraints (executor hook) resolve
        against it at trace time, and watchdog post-mortems report it."""
        from .mesh import set_current_mesh
        set_current_mesh(self.spec)

    def _tracing_on_mesh(self):
        """Scope for everything that may trace the step: inside it jax's
        own mesh context names this trainer's mesh, which is how an op
        that must place itself by hand (the Pallas attention kernels,
        ops/nn.py ``_per_mesh_shard``) knows the program being traced
        spans it.  The armed MeshSpec above outlives the trace; this does
        not, so a later single-device call is not mistaken for ours."""
        self._arm_mesh()
        return jax.set_mesh(self.spec.mesh)

    # -- state ------------------------------------------------------------
    def init_state(self, shapes: Dict[str, tuple], initializer=None,
                   seed=0):
        """Initialise (params, mom, aux) replicated on the mesh."""
        self._arm_mesh()
        from ..executor import _resolve_structs
        from ..initializer import Xavier, InitDesc
        from ..ndarray.ndarray import NDArray
        import numpy as _np
        prog, known, _ = _resolve_structs(self.symbol, shapes)
        self._last_shapes = dict(shapes)
        self._param_shapes = {n: tuple(known[n].shape)
                              for n in self.param_names if n in known}
        initializer = initializer or Xavier(rnd_type="gaussian",
                                            factor_type="in", magnitude=2)
        rep = self.spec.replicated()
        params = []
        # deterministic init independent of global RNG history
        from .. import rng as _rng_mod
        saved = (_rng_mod._get().key, _rng_mod._get().counter)
        _rng_mod.seed(seed)
        for n in self.param_names:
            s = known[n]
            host = _np.zeros(s.shape, _np.float32)
            arr = NDArray(jnp.asarray(host))
            try:
                initializer(InitDesc(n), arr)
                host = arr.asnumpy()
            except Exception:
                pass
            if self.param_dtype is not None and not n.endswith(
                    ("gamma", "beta")):  # BN affine stays fp32
                from ..base import dtype_np
                dt = dtype_np(self.param_dtype)
            else:
                dt = s.dtype
            params.append(jax.device_put(
                host.astype(dt), self.param_sharding(n, s.shape)))
        _rng_mod._get().key, _rng_mod._get().counter = saved
        mom = tuple(jax.device_put(np.zeros(known[n].shape, np.float32),
                                   self.mom_sharding(n, known[n].shape))
                    for n in self.param_names)
        # a moving variance starts at one; a moving mean, and an expert
        # layer's selection bias and load (_contrib_moe_ffn), at zero
        zero_aux = ("expert_bias", "expert_load")
        aux = tuple(jax.device_put(
            (np.zeros if "mean" in n or n.endswith(zero_aux)
             else np.ones)(known[n].shape, np.float32),
            rep) for n in self.prog.aux_names)
        # memory plane: bucket the trainer's persistent state so live-HBM
        # accounting and OOM forensics can name it (one bool when off)
        from ..telemetry import memory as _memory
        _memory.tag(params, "params", label="ShardedTrainer")
        _memory.tag(mom, "optimizer", label="ShardedTrainer.mom")
        _memory.tag(aux, "params", label="ShardedTrainer.aux")
        return tuple(params), mom, aux

    # -- the step ---------------------------------------------------------
    def _make_step_fn(self):
        """The raw (un-jitted) fused fwd+bwd+SGD step, with the non-finite
        guard and loss-scale automaton compiled in.

        ``guard`` is ``(scale f32, good-streak i32)``.  The loss is
        multiplied by ``scale`` before the backward and the gradients
        divided back in the update, so under- and overflow in low-precision
        graphs are steerable; the ``isfinite`` verdict reduces over the loss
        and every (already psum-reduced) gradient inside the same program —
        every dp replica computes the identical verdict from the identical
        reduced gradients, so the skip/keep select stays SPMD-consistent
        with no extra collective.  A bad step keeps params/mom/aux
        unchanged and halves the scale; good steps grow it back."""
        from ..resilience import guards as _guards
        prog = self.prog
        param_idx = list(self.param_idx)
        input_idx = dict(self.input_idx)
        lr, momentum, wd = self.lr, self.momentum, self.wd
        dynamic = self.dynamic_loss_scale
        growth_interval = self.loss_scale_growth_interval

        def loss_fn(params, inputs, aux, keys):
            args = [None] * len(prog.arg_names)
            for i, p in zip(param_idx, params):
                args[i] = p
            for n, v in inputs.items():
                args[input_idx[n]] = v
            outs, new_aux = prog.evaluate(args, aux, keys, True)
            # SoftmaxOutput-style heads carry their gradient via custom vjp;
            # summing outputs triggers it exactly like executor backward
            loss = sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
            return loss, (outs, new_aux)

        from ..executor import _remat_wrap
        loss_fn = _remat_wrap(loss_fn, self._built_remat)

        def scaled_loss_fn(params, inputs, aux, keys, scale):
            loss, extra = loss_fn(params, inputs, aux, keys)
            return loss * scale, (loss, extra)

        accum = self.grad_accum
        num_rng = prog.num_rng
        # ZeRO sharded weight update: constraining every gradient to its
        # optimizer-state sharding makes GSPMD reduce each replica's
        # partial straight into the owned dp shard (reduce-scatter on the
        # wire) and run the whole update chain below — momentum, weight
        # decay, the non-finite select — at shard shapes (1/dp FLOPs and
        # bytes per chip); the final constraint back to the parameter
        # sharding is the weight all-gather, one per parameter, each
        # independent of every other parameter's update so the scheduler
        # can overlap it (the PR-9 static instrument classifies them
        # pipelined).  Params with no dp-divisible free dim keep their
        # plain all-reduce — GC305 polices whether those bytes matter.
        zero = self.shard_weight_update
        zspecs = self._mom_shardings() if zero else None
        pspecs = self._param_shardings() if zero else None

        def shard_grads(grads):
            if not zero:
                return grads
            with jax.named_scope("mx.zero_scatter"):
                return tuple(_placement.constrain(g, s)
                             for g, s in zip(grads, zspecs))

        def step_fn(params, mom, aux, inputs, keys, guard):
            scale, good = guard
            if accum == 1:
                (_, (loss, (outs, new_aux))), grads = jax.value_and_grad(
                    scaled_loss_fn, argnums=0, has_aux=True)(
                        params, inputs, aux, keys, scale)
                grads = shard_grads(grads)
            else:
                # gradient accumulation: inputs carry a leading micro
                # dim (accum, micro_bs, ...); scan folds the micro
                # grads into one f32 accumulator (the memory point of
                # accumulation — one micro-batch of activations live at
                # a time) and aux (BN stats) threads through micros
                # exactly like consecutive steps would.  Loss heads
                # carry per-sample gradients (normalization='null'), so
                # the summed grads equal one big (accum*micro)-batch
                # step bit-for-bit up to fp reassociation.
                def micro_step(carry, micro_inputs):
                    grads_c, aux_c, loss_c, i = carry
                    keys_i = (jax.vmap(
                        lambda k: jax.random.fold_in(k, i))(keys)
                        if num_rng else keys)
                    (_, (loss_i, (_outs, aux_n))), g = jax.value_and_grad(
                        scaled_loss_fn, argnums=0, has_aux=True)(
                            params, micro_inputs, aux_c, keys_i, scale)
                    # with ZeRO each micro's partial reduces straight
                    # into the dp shard, so the f32 accumulator itself
                    # lives sharded (1/dp accumulator HBM) and only ONE
                    # weight all-gather pays for all `accum` reductions
                    grads_c = tuple(gc + gi.astype(jnp.float32)
                                    for gc, gi in zip(grads_c,
                                                      shard_grads(g)))
                    return (grads_c, aux_n, loss_c + loss_i, i + 1), None
                init = (shard_grads(tuple(jnp.zeros(p.shape, jnp.float32)
                                          for p in params)),
                        aux, jnp.float32(0.0), jnp.int32(0))
                (grads, new_aux, loss, _), _ = jax.lax.scan(
                    micro_step, init, inputs)
            # stable device-side names (jax.named_scope: metadata only)
            # so a trace can put device time down to the update, the
            # guard, the loss-scale automaton and the ZeRO gather
            with jax.named_scope("mx.update"):
                new_params, new_mom = _tree_sgd(
                    params, grads, mom, lr, momentum, wd, 1.0 / scale)
            with jax.named_scope("mx.guard"):
                ok = _guards.all_finite(loss, grads)
                new_params = tuple(jnp.where(ok, np_, p)
                                   for np_, p in zip(new_params, params))
            if zero:
                # the weight all-gather: shard-updated params return to
                # their parameter sharding (replicated over dp)
                with jax.named_scope("mx.zero_gather"):
                    new_params = tuple(
                        _placement.constrain(np_, s)
                        for np_, s in zip(new_params, pspecs))
            with jax.named_scope("mx.guard"):
                new_mom = tuple(jnp.where(ok, nm, m)
                                for nm, m in zip(new_mom, mom))
                new_aux = tuple(jnp.where(ok, na, a)
                                for na, a in zip(new_aux, aux))
            with jax.named_scope("mx.loss_scale"):
                new_scale, new_good = _guards.scale_update(
                    scale, good, ok, growth_interval, dynamic=dynamic)
            return (new_params, new_mom, new_aux, loss, ok,
                    (new_scale, new_good))

        return step_fn

    def _state_shardings(self):
        rep = self.spec.replicated()
        return (self._param_shardings(), self._mom_shardings(),
                tuple(rep for _ in self.prog.aux_names))

    def _batch_in_sharding(self):
        """Input sharding for one batch tensor: dp over dim 0, or — with
        grad accumulation — dp over dim 1 under the unsharded micro
        dim the in-jit scan walks."""
        if self.grad_accum > 1:
            return NamedSharding(self.spec.mesh,
                                 P(None, self.spec.dp_axis))
        return self.spec.batch_sharding()

    def _build_step(self, donate=None):
        if donate is None:
            # deserialized executables with donated (aliased) buffers
            # compute wrong results on backends whose runtime never
            # implemented donation (XLA:CPU) — with the compile cache
            # armed there, build donation-free: identical numerics AND
            # identical cost (the runtime was ignoring the donation
            # anyway), and the executable round-trips the cache safely
            # (compile/cache.py donation_safe).
            from .. import compile as _cc
            donate = not (_cc.enabled() and not _cc.donation_safe())
        self._arm_mesh()
        step_fn = self._make_step_fn()
        rep = self.spec.replicated()
        bat = self._batch_in_sharding()
        pshard, mshard, ashard = self._state_shardings()
        in_shardings = (
            pshard,                                 # params (tp-aware)
            mshard,                                 # mom (ZeRO: +dp-sharded)
            ashard,                                 # aux
            {n: bat for n in self.input_names},     # batch
            rep,                                    # keys
            (rep, rep),                             # guard (scale, streak)
        )
        out_shardings = (pshard, mshard, ashard, rep, rep, (rep, rep))
        self._step_donated = bool(donate)   # preflight GC202 checks this
        with self.spec.mesh:
            return jax.jit(step_fn, in_shardings=in_shardings,
                           out_shardings=out_shardings,
                           donate_argnums=(0, 1, 2, 5) if donate else ())

    def build_step_auto_layout(self, params, mom, aux, batch_shapes,
                               input_dtypes=None):
        """Compile the step letting XLA pick the PARAMETER LAYOUTS, then
        re-lay the state once to match; returns
        (compiled_step, params, mom, aux).

        Why: with NCHW/OIHW graphs the default (row-major) parameter
        layout differs from the layout TPU convolutions want, and with
        fixed input layouts + donation XLA inserts a layout-conversion
        copy of EVERY conv weight and its momentum EVERY step (~250
        copies/step on ResNet-50, measured via tools/hlo_diff.py — a
        fixed ~2.5 ms/step tax at any batch size).  AUTO layouts let the
        compiler store each parameter the way its consumers read it, so
        the donated update aliases cleanly.  Batch inputs and rng keys
        keep default layouts (they arrive fresh from the host each
        step)."""
        try:
            from jax.experimental.layout import Format, Layout
        except ImportError:     # jax <= 0.4.x: pre-rename names
            from jax.experimental.layout import (
                DeviceLocalLayout as Layout, Layout as Format)

        self._arm_mesh()
        step_fn = self._make_step_fn()
        rep = self.spec.replicated()
        bat = self._batch_in_sharding()
        pshard, mshard, ashard = self._state_shardings()

        def auto(shardings):
            return tuple(Format(Layout.AUTO, s) for s in shardings)

        in_shardings = (auto(pshard), auto(mshard), auto(ashard),
                        {n: bat for n in self.input_names}, rep, (rep, rep))
        out_shardings = (auto(pshard), auto(mshard), auto(ashard), rep, rep,
                         (rep, rep))

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        # AOT-compiled executables are dtype-exact: callers feeding
        # non-f32 batches (e.g. the uint8 RecordIO path) must say so
        dts = input_dtypes or {}
        inputs = {n: jax.ShapeDtypeStruct(tuple(batch_shapes[n]),
                                          dts.get(n, jnp.float32))
                  for n in self.input_names}
        self._maybe_preflight(params, mom, aux, inputs)
        keys = self._keys()
        guard = self._guard_arrays()
        from .. import telemetry as _tel
        from .. import compile as _cc
        # same donation rule as _build_step: donation-free when the
        # cache is armed on a backend that never implemented donation
        donate_argnums = ((0, 1, 2, 5)
                          if not (_cc.enabled() and not _cc.donation_safe())
                          else ())
        # everything here that carries a layout — the step and the re-lay
        # programs below — stays out of jax's persistent cache
        # (compile/cache.outside_jax_cache says why)
        with self._tracing_on_mesh(), _cc.outside_jax_cache():
            jitted = jax.jit(step_fn, in_shardings=in_shardings,
                             out_shardings=out_shardings,
                             donate_argnums=donate_argnums)
            with _tel.span("compile/auto_layout", cat="compile",
                           metric="compile.seconds", timed=True) as _cs:
                lowered = jitted.lower(
                    tuple(sds(p) for p in params),
                    tuple(sds(m) for m in mom),
                    tuple(sds(a) for a in aux), inputs, sds(keys),
                    (sds(guard[0]), sds(guard[1])))
                compiled, cc_result = _cc.cached_compile(
                    lowered, "auto_layout", mesh=self.spec.mesh)
                _cs.attrs["result"] = cc_result
            p_fmt, m_fmt, a_fmt = compiled.input_formats[0][:3]
            params, mom, aux = (
                tuple(_relaid(x, f) for x, f in zip(state, fmt))
                for state, fmt in ((params, p_fmt), (mom, m_fmt),
                                   (aux, a_fmt)))
        _tel.tracing.note_compile("train_step_auto_layout", _cs.duration,
                                  symbol=self.symbol.name or "symbol",
                                  result=cc_result)
        from ..telemetry import perf as _perf
        _perf.maybe_attribute(
            compiled,
            "ShardedTrainer.auto_layout(%s)" % (self.symbol.name
                                                or "symbol"),
            n_devices=self.spec.mesh.size, ring_n=self.spec.dp_size,
            mesh=self.spec.mesh)
        from ..telemetry import memory as _memory
        if _memory.enabled():
            # re-laid state carries fresh buffers; re-tag them and record
            # this program's compiled memory breakdown for OOM forensics
            _memory.tag(params, "params", label="ShardedTrainer")
            _memory.tag(mom, "optimizer", label="ShardedTrainer.mom")
            _memory.tag(aux, "params", label="ShardedTrainer.aux")
            _memory.note_program(
                "ShardedTrainer.auto_layout(%s)" % (self.symbol.name
                                                    or "symbol"), compiled)
        return compiled, params, mom, aux

    def _compile_step_cached(self, params, mom, aux, inputs, keys):
        """First-step compile through the persistent executable cache
        (mxnet_tpu/compile): returns ``(compiled_or_None, result)`` with
        ``result`` in hit/miss/off.  ``None`` means "dispatch through
        the jit as before" — the cache disabled, or any cache-path
        failure (which must degrade to the stock path, never break a
        step)."""
        from .. import compile as _cc
        if not _cc.enabled():
            return None, "off"
        try:
            def sds(x):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            structs = jax.tree_util.tree_map(
                sds, (params, mom, aux, inputs, keys,
                      self._guard_arrays()))
            with self._tracing_on_mesh():
                lowered = self._step.lower(*structs)
            compiled, result = _cc.cached_compile(
                lowered, "train_step", mesh=self.spec.mesh)
            return compiled, result
        except Exception:
            import logging
            logging.exception("compile-cache: trainer step path failed; "
                              "dispatching through jit")
            return None, "off"

    def clone(self, spec: Optional[MeshSpec] = None,
              grad_accum: Optional[int] = None) -> "ShardedTrainer":
        """A sibling trainer with the same symbol/hyperparameters over a
        (possibly different) mesh — the standby pre-compiler's shadow:
        its step program IS the program a post-resize trainer of that
        spec would build, so pre-compiling it warms the real thing."""
        return ShardedTrainer(
            self.symbol, spec if spec is not None else self.spec,
            data_names=self.data_names, label_names=self.label_names,
            lr=self.lr, momentum=self.momentum, wd=self.wd,
            loss_scale=self.init_loss_scale, param_dtype=self.param_dtype,
            shard_optimizer_state=self.shard_optimizer_state,
            dynamic_loss_scale=self.dynamic_loss_scale,
            loss_scale_growth_interval=self.loss_scale_growth_interval,
            nonfinite_budget=self.nonfinite_budget,
            guard_nonfinite=self.guard_nonfinite,
            grad_accum=(grad_accum if grad_accum is not None
                        else self.grad_accum),
            zero=self.zero)

    def lower_step_for(self, devices, grad_accum, state, batch_shapes,
                       input_dtypes=None):
        """Lower the step program as it would exist over ``devices``
        with ``grad_accum`` — the warm-standby entry point
        (compile/standby.py).  ``state`` is this trainer's live
        ``(params, mom, aux)`` (shapes/dtypes are world-independent);
        ``batch_shapes`` are the GLOBAL per-update input shapes.
        Returns ``(lowered, mesh)``; the lowered text is identical to
        what the post-resize trainer's first step will lower, which is
        what makes the cache key match."""
        from .mesh import reform_mesh
        spec = reform_mesh(self.spec, generation=self.spec.generation + 1,
                           devices=devices)
        shadow = self.clone(spec=spec, grad_accum=grad_accum)
        self._param_shardings()          # resolve parent shapes once
        shadow._param_shapes = dict(self._param_shapes or {})
        shadow._last_shapes = dict(getattr(self, "_last_shapes", {}) or {})
        params, mom, aux = state

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        dts = input_dtypes or {}
        accum = shadow.grad_accum
        input_sds = {}
        for n in shadow.input_names:
            shape = tuple(batch_shapes[n])
            if accum > 1:
                if shape[0] % accum:
                    raise ValueError(
                        "global batch dim %d of %r is not divisible by "
                        "grad_accum=%d" % (shape[0], n, accum))
                shape = (accum, shape[0] // accum) + shape[1:]
            input_sds[n] = jax.ShapeDtypeStruct(
                shape, dts.get(n, jnp.float32))
        num_rng = shadow.prog.num_rng
        keys_sds = jax.ShapeDtypeStruct((num_rng if num_rng else 0, 2),
                                        jnp.uint32)
        guard_sds = (jax.ShapeDtypeStruct((), jnp.float32),
                     jax.ShapeDtypeStruct((), jnp.int32))
        jitted = shadow._build_step()
        try:
            with shadow._tracing_on_mesh():
                lowered = jitted.lower(
                    tuple(sds(p) for p in params),
                    tuple(sds(m) for m in mom),
                    tuple(sds(a) for a in aux),
                    input_sds, keys_sds, guard_sds)
        finally:
            self._arm_mesh()             # _build_step armed the shadow's
        return lowered, spec.mesh

    def set_grad_accum(self, accum: int):
        """Change the gradient-accumulation factor (one optimizer update
        per ``accum`` micro-batches).  The elastic resize path calls this
        after a world-size change so ``world * micro_batch * accum`` —
        the GLOBAL batch — stays constant.  Rebuilds the step program on
        next use; returns self."""
        accum = int(accum)
        if accum < 1:
            raise ValueError("grad_accum must be >= 1, got %r" % accum)
        if accum != self.grad_accum:
            self.grad_accum = accum
            self._step = None
            self._step_exec = None
        return self

    def _prepare_batch(self, batch):
        """Host-side batch shaping: with grad accumulation the per-update
        batch (accum*micro, ...) folds into (accum, micro, ...) so the
        in-jit scan walks the leading dim."""
        accum = self.grad_accum
        out = {}
        for n, v in batch.items():
            v = np.asarray(v) if not hasattr(v, "reshape") else v
            if accum > 1:
                if v.shape[0] % accum:
                    raise ValueError(
                        "batch dim %d of %r is not divisible by "
                        "grad_accum=%d" % (v.shape[0], n, accum))
                v = v.reshape((accum, v.shape[0] // accum) + v.shape[1:])
            out[n] = v
        return out

    def _put_batch(self, v, local_batch):
        """Device placement for one (already accum-folded) batch tensor.
        ``local_batch``: v is this PROCESS's shard of the global batch
        (multi-host data loading — each rank reads only its part); the
        global array is assembled across processes without any host
        gather."""
        sharding = self._batch_in_sharding()
        if local_batch:
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(v))
        return jax.device_put(v, sharding)

    def step(self, params, mom, aux, batch: Dict[str, np.ndarray],
             local_batch: bool = False):
        """One synchronous data-parallel SGD step (one optimizer update =
        ``grad_accum`` micro-batches).  batch arrays are global (host)
        arrays sharded over dp — or, with ``local_batch=True``, each
        process's own shard of the global batch.

        Resilience semantics: a non-finite loss/grad step applies NO
        update (params/mom/aux come back unchanged), backs the loss scale
        off, and — after ``nonfinite_budget`` consecutive bad steps —
        raises :class:`~mxnet_tpu.resilience.guards.NonFiniteError` with
        diagnostics.  Chaos faults (`preempt`, `nan_grad`) are honored
        here so fault drills exercise this exact code path."""
        from .. import telemetry as _tel
        from ..executor import backward_mirror_policy
        from ..resilience import chaos as _chaos
        from ..resilience import watchdog as _watchdog
        from ..telemetry import memory as _memory
        from .audit import record_collective
        self._arm_mesh()
        remat = backward_mirror_policy()
        fresh_program = self._step is None or remat != self._built_remat
        if fresh_program:
            self._built_remat = remat
            self._step = self._build_step()
            self._step_exec = None
        self._step_count += 1
        _chaos.maybe_preempt(self._step_count)
        if _chaos.fire("nan_grad", self._step_count) is not None:
            # poison the batch so the REAL in-step detector trips — the
            # drill proves detection, not a shortcut flag
            poison = self.data_names[0]
            batch = dict(batch)
            batch[poison] = np.full_like(np.asarray(batch[poison]), np.nan)
        batch = self._prepare_batch(batch)
        if not self._preflight_done:
            # trace-check with GLOBAL shapes: under local_batch each
            # process only holds its shard, but the program is SPMD
            mul = jax.process_count() if local_batch else 1
            bdim = 1 if self.grad_accum > 1 else 0
            sds = {}
            for n, v in batch.items():
                shape = list(np.asarray(v).shape)
                shape[bdim] *= mul
                sds[n] = jax.ShapeDtypeStruct(tuple(shape),
                                              np.asarray(v).dtype)
            self._maybe_preflight(params, mom, aux, sds)
        # the deadline covers everything a stall can hide in: the chaos
        # hang drill, host->device transfer, and the jitted step with its
        # fused gradient psum (a dead peer blocks right here); the oom
        # guard turns an allocator RESOURCE_EXHAUSTED anywhere inside
        # into a post-mortem naming the live buffers + this program
        _prog_name = "ShardedTrainer.step(%s)" % (self.symbol.name
                                                  or "symbol")
        with _tel.span("train/step", cat="train",
                       metric="train.step_seconds",
                       step=self._step_count) as _sp, \
                _watchdog.watch("ShardedTrainer.step", kind="step",
                                step=self._step_count), \
                _memory.oom_guard("ShardedTrainer.step",
                                  program=_prog_name,
                                  step=self._step_count):
            _chaos.maybe_hang(self._step_count)
            _chaos.maybe_oom(self._step_count)
            with _tel.span("train/host_enqueue", cat="train",
                           metric="train.host_enqueue_seconds",
                           step=self._step_count):
                inputs = {n: self._put_batch(v, local_batch)
                          for n, v in batch.items()}
                _memory.tag(inputs, "batch", label="ShardedTrainer.step")
                keys = self._keys()
                # compile/ span family (ROADMAP item 5): the first call
                # of a freshly-built jitted step is where trace + lower
                # + compile happen (dispatch is async — the device time
                # lands in train/device_wait, not here), so its duration
                # IS the compile cost; timed=True keeps the ungated
                # compile_seconds ledger extra working when disarmed
                _cspan = (_tel.span("compile/train_step", cat="compile",
                                    metric="compile.seconds", timed=True,
                                    step=self._step_count)
                          if fresh_program else contextlib.nullcontext())
                with _cspan:
                    cc_result = "off"
                    if fresh_program:
                        # persistent compile cache (mxnet_tpu/compile):
                        # when armed, the first step deserializes a warm
                        # executable instead of compiling — the elastic
                        # resume path pays zero compile after a resize
                        self._step_exec, cc_result = \
                            self._compile_step_cached(
                                params, mom, aux, inputs, keys)
                    if self._step_exec is not None:
                        params, mom, aux, loss, ok, guard = \
                            self._step_exec(params, mom, aux, inputs,
                                            keys, self._guard_arrays())
                    else:
                        # the first call traces, and so does any
                        # later one that brings a new batch shape
                        with self._tracing_on_mesh():
                            params, mom, aux, loss, ok, guard = self._step(
                                params, mom, aux, inputs, keys,
                                self._guard_arrays())
                if fresh_program:
                    from ..telemetry import tracing as _tracing
                    _cspan.attrs["result"] = cc_result
                    _tracing.note_compile(
                        "train_step", _cspan.duration,
                        symbol=self.symbol.name or "symbol",
                        result=cc_result)
                self._guard_state = guard
            # host-enqueue vs device-block split: the dispatch above is
            # async; this wait is where device time (and a straggling
            # peer's psum) actually lands.  The explicit sync happens
            # only when spans record — the disarmed hot path keeps the
            # pipelined async dispatch untouched.
            with _tel.span("train/device_wait", cat="train",
                           metric="train.device_wait_seconds",
                           step=self._step_count) as _dw:
                if _dw.active:
                    jax.block_until_ready((loss, ok))
                if self.guard_nonfinite:
                    self._note_step_result(bool(ok), loss)
        _tel.count("train.steps")
        if self.shard_weight_update:
            shardable, residual = self._zero_split_bytes()
            record_collective(
                "reduce-scatter", "ShardedTrainer.step ZeRO grad "
                "reduce-scatter", step=self._step_count, bytes=shardable)
            record_collective(
                "all-gather", "ShardedTrainer.step ZeRO weight all-gather",
                step=self._step_count, bytes=shardable)
            if residual:
                record_collective(
                    "psum", "ShardedTrainer.step residual grad all-reduce "
                    "(no dp-divisible dim)", step=self._step_count,
                    bytes=residual)
        else:
            record_collective("psum",
                              "ShardedTrainer.step dp grad all-reduce",
                              step=self._step_count,
                              bytes=self._grad_bytes())
        _watchdog.heartbeat(self._step_count)
        _tel.window_tick()
        if _memory.enabled():
            # donated updates return fresh buffers each step: keep them
            # bucketed, tick the memory timeline + leak watchdog, and
            # make sure the background sampler runs (armed only)
            _memory.tag(params, "params", label="ShardedTrainer")
            _memory.tag(mom, "optimizer", label="ShardedTrainer.mom")
            _memory.tag(aux, "params", label="ShardedTrainer.aux")
            _memory.note_step(self._step_count)
            _memory.maybe_start_sampler()
        self._maybe_attribute_step(params, mom, aux, inputs, keys)
        return params, mom, aux, loss

    def _maybe_attribute_step(self, params, mom, aux, inputs, keys):
        """Opt-in attribution of the lazily-jitted step program (the
        build_step_auto_layout path attributes its Compiled directly).
        Runs once, a few steps in (MXNET_TPU_ATTRIBUTION_AFTER), so the
        train.step_seconds/host_enqueue/device_wait histograms already
        hold measurements for the report's measured side."""
        from ..telemetry import perf as _perf
        if self._attribution_done or not _perf.enabled():
            return
        if self._step_count < _perf.attribute_after_steps():
            return
        self._attribution_done = True

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        try:
            structs = jax.tree_util.tree_map(
                sds, (params, mom, aux, inputs, keys,
                      self._guard_arrays()))
            with self._tracing_on_mesh():
                compiled = self._step.lower(*structs).compile()
        except Exception:
            import logging
            logging.exception("attribution: step lowering failed "
                              "(continuing)")
            return
        _perf.maybe_attribute(
            compiled,
            "ShardedTrainer.step(%s)" % (self.symbol.name or "symbol"),
            n_devices=self.spec.mesh.size, ring_n=self.spec.dp_size,
            mesh=self.spec.mesh)

    def _zero_split_bytes(self):
        """Split the f32 grad payload into (dp-shardable, residual)
        bytes under the ZeRO update: shardable params reduce-scatter +
        all-gather, the rest (no dp-divisible free dim) keep a plain
        all-reduce.  Feeds the collective telemetry records and the
        audit's analytic model."""
        shapes = self._param_shapes or {}
        dp = self.spec.dp_size
        shardable = residual = 0
        for n in self.param_names:
            shape = shapes.get(n, ())
            nbytes = 4 * int(np.prod(shape)) if shape else 4
            base = self.param_sharding(n, shape)
            dims = list(base.spec) + [None] * (len(shape) - len(base.spec))
            if _placement.zero_shard_dim(shape, dims, dp) is not None:
                shardable += nbytes
            else:
                residual += nbytes
        return shardable, residual

    def _grad_bytes(self):
        """Analytic dp all-reduce payload (f32 grads), cached — feeds the
        collective telemetry record; None before shapes resolve."""
        cached = getattr(self, "_grad_bytes_cache", None)
        if cached is not None:
            return cached
        shapes = self._param_shapes
        if not shapes:
            return None
        total = 0
        for shape in shapes.values():
            n = 1
            for d in shape:
                n *= int(d)
            total += 4 * n
        self._grad_bytes_cache = total
        return total

    def _note_step_result(self, ok, loss):
        """Host half of the guard: budget tracking + graceful abort."""
        self._last_ok = ok
        if ok:
            self._bad_streak = 0
            return
        self._bad_streak += 1
        self._skipped_steps += 1
        from .. import telemetry as _tel
        _tel.count("train.skipped_steps")
        if self._bad_streak > self.nonfinite_budget:
            from ..resilience.guards import NonFiniteError
            raise NonFiniteError(
                "aborting training: %d consecutive non-finite steps "
                "exceeded the budget of %d at step %d (loss=%r, loss "
                "scale now %.4g; %d steps skipped in total).  Restore "
                "the latest checkpoint with a lower lr, or raise "
                "MXNET_TPU_NONFINITE_BUDGET."
                % (self._bad_streak, self.nonfinite_budget,
                   self._step_count, float(loss), self.loss_scale,
                   self._skipped_steps),
                diagnostics={"step": self._step_count,
                             "loss_scale": self.loss_scale,
                             "bad_streak": self._bad_streak,
                             "skipped_steps": self._skipped_steps})

    # -- pre-flight --------------------------------------------------------
    def _maybe_preflight(self, params, mom, aux, batch):
        """Static analysis of the step program before step 0 (opt-in via
        MXNET_TPU_PREFLIGHT=1; analysis/preflight.py).  Trace-only — no
        compile, no device execution — and once per trainer.  Raises
        PreflightError on ERROR-severity findings (action=abort)."""
        if self._preflight_done:
            return
        self._preflight_done = True
        from ..analysis import preflight as _preflight
        if not _preflight.enabled():
            return
        inputs = {n: (v if hasattr(v, "shape") and hasattr(v, "dtype")
                      else np.asarray(v))
                  for n, v in batch.items()}
        inputs = {n: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
                  for n, v in inputs.items()}
        _preflight.run_trainer_preflight(self, params, mom, aux, inputs)

    # -- resilience state --------------------------------------------------
    def _guard_arrays(self):
        """(scale, good-streak) device scalars, created on first use."""
        if self._guard_state is None:
            rep = self.spec.replicated()
            self._guard_state = (
                jax.device_put(jnp.float32(self.init_loss_scale), rep),
                jax.device_put(jnp.int32(0), rep))
        return self._guard_state

    @property
    def loss_scale(self) -> float:
        return float(self._guard_state[0]) if self._guard_state is not None \
            else self.init_loss_scale

    @property
    def skipped_steps(self) -> int:
        return self._skipped_steps

    def resilience_meta(self) -> Dict[str, float]:
        """Guard/progress state a checkpoint must carry to resume
        faithfully (consumed by resilience.checkpoint.save_trainer)."""
        good = int(self._guard_state[1]) if self._guard_state is not None \
            else 0
        return {"loss_scale": self.loss_scale, "good_streak": good,
                "step_count": self._step_count,
                "skipped_steps": self._skipped_steps}

    def set_resilience_state(self, meta):
        """Restore the guard automaton from checkpoint meta."""
        rep = self.spec.replicated()
        self._guard_state = (
            jax.device_put(jnp.float32(meta.get("loss_scale",
                                                self.init_loss_scale)), rep),
            jax.device_put(jnp.int32(meta.get("good_streak", 0)), rep))
        self._step_count = int(meta.get("step_count", 0))
        self._skipped_steps = int(meta.get("skipped_steps", 0))
        self._bad_streak = 0

    def _keys(self):
        from .. import rng as _rng
        rep = self.spec.replicated()
        if self.prog.num_rng == 0:
            return jax.device_put(jnp.zeros((0, 2), jnp.uint32), rep)
        return jax.device_put(
            jnp.stack([_rng.next_key() for _ in range(self.prog.num_rng)]),
            rep)


def sgd_step_fn(trainer: ShardedTrainer):
    """Expose the raw jitted step (bench/dryrun path).  Signature:
    ``step(params, mom, aux, inputs, keys, guard) -> (params, mom, aux,
    loss, ok, guard)`` where ``guard`` comes from
    ``trainer._guard_arrays()``.  Buffers are donated — params/mom/aux/
    guard update in place in HBM; callers must rebind their references to
    the returned state every call."""
    if trainer._step is None:
        trainer._step = trainer._build_step()
    return trainer._step
