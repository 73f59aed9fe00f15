"""Pipeline parallelism — micro-batched GPipe schedule over a 'pp' mesh axis.

The reference's model parallelism is sequential layer placement with
_CrossDeviceCopy (graph_executor.cc:313-436, example/model-parallel/lstm) —
device i idles while device j computes.  This module provides the thing the
reference lacks (SURVEY.md §2.3: "No pipelining of micro-batches"): stages
run concurrently on different micro-batches, boundary activations hop one
ring step per tick via lax.ppermute.

Model: `stage_fn(stage_id, params, x) -> y` applied on every device under
shard_map; each device runs its own stage's parameters.  The driver loop
runs M + 2(S - 1) ticks (S stages, M micro-batches), scanning over a
rotating buffer; boundary activations are sent one tick AFTER they are
computed, so every ppermute has a full tick of independent stage compute
to hide behind (collective/compute overlap — the send is off the critical
path).  Backward comes from jax.grad THROUGH the whole schedule — XLA
differentiates the scan+ppermute program, giving 1F1B-equivalent comms.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

__all__ = ["pipeline_apply", "PipelineRunner"]


def pipeline_apply(stage_fn: Callable, num_stages: int, mesh: Mesh,
                   axis: str, params_stacked, x_micro):
    """Run micro-batches through the stage pipeline.

    stage_fn(params_slice, x) -> y   (same shapes for x and y)
    params_stacked: pytree with leading axis == num_stages (stage i's params)
    x_micro: (M, mb, ...) micro-batched input (global).
    Returns (M, mb, ...) outputs after all stages.

    ``mesh`` may be a Mesh or MeshSpec and may carry other axes (the
    unified dp×tp×pp mesh): the shard_map — retained hand-written
    because a GPipe tick schedule is inherently MPMD-in-time and no
    sharding annotation produces one — is manual only over ``axis`` and
    composes with the GSPMD-managed axes.
    """
    from .placement import as_mesh
    mesh = as_mesh(mesh)
    M = x_micro.shape[0]
    S = num_stages

    def per_device(params_local, x_all):
        # params_local: this device's stage params — shard_map keeps the
        # (sharded) leading stage axis as size 1; squeeze it off
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = x_all.shape[1:]
        # Overlapped schedule: each boundary activation is SENT one tick
        # after it is computed, so the ppermute's operand comes from the
        # carry and its result is consumed only next tick — the hop has
        # a FULL tick of stage compute that is neither its ancestor nor
        # its descendant to hide behind (the old compute->send->consume
        # tick chained every hop on the critical path: the static
        # overlap instrument read it 0% overlappable).  Stage s runs
        # micro-batch m at tick m + 2s; the fill/drain grows by S-1
        # ticks, amortized at M >> S while EVERY hop is hidden.
        T = M + 2 * (S - 1)

        def tick(carry, t):
            y_send, buf, outputs = carry
            # transfer plane first: forward LAST tick's activation
            # (independent of everything computed this tick)
            perm = [(j, (j + 1) % S) for j in range(S)]
            buf_next = jax.lax.ppermute(y_send, axis, perm)
            # stage 0 ingests micro-batch t (if in range); others take
            # the activation received at the END of the previous tick
            x_in = jnp.where(t < M, x_all[jnp.minimum(t, M - 1)],
                             jnp.zeros(mb_shape, x_all.dtype))
            inp = jnp.where(stage == 0, x_in, buf)
            y = stage_fn(params_local, inp)
            # last stage computes micro-batch (t - 2(S-1)) at tick t
            emit_idx = t - 2 * (S - 1)
            is_emit = (stage == S - 1) & (emit_idx >= 0)
            outputs = jnp.where(
                is_emit,
                outputs.at[jnp.maximum(emit_idx, 0)].set(y),
                outputs)
            return (y, buf_next, outputs), None

        # the scan carries become device-varying on the first tick, so
        # their zero initial values must be marked varying too (check_vma)
        def varying_zeros(shape):
            return jax.lax.pcast(jnp.zeros(shape, x_all.dtype), (axis,),
                                 to="varying")
        y0 = varying_zeros(mb_shape)
        buf0 = varying_zeros(mb_shape)
        outs0 = varying_zeros((M,) + mb_shape)
        (_, _, outputs), _ = jax.lax.scan(tick, (y0, buf0, outs0),
                                          jnp.arange(T))
        # only the last stage holds real outputs; broadcast them ring-wide
        outputs = jax.lax.psum(
            jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)),
            axis)
        return outputs

    in_specs = (P(axis), P())       # params sharded by stage; x replicated
    out_specs = P()
    mapped = shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    from .. import telemetry as _tel
    from ..resilience import watchdog as _wd
    from .audit import record_collective
    # boundary activations hop the ring once per tick: (M + 2(S-1))
    # micro-batch-sized ppermutes; the final psum moves the (M, mb)
    # outputs
    act_bytes = int(getattr(x_micro, "nbytes", 0))
    hop_bytes = (act_bytes // max(M, 1)) * (M + 2 * (S - 1))
    with _tel.span("collective/pipeline_apply", cat="collective",
                   metric="parallel.collective_seconds",
                   kind="collective-permute,all-reduce",
                   bytes=hop_bytes + act_bytes), \
            _wd.watch("parallel.pipeline_apply", kind="collective"):
        params_sharded = jax.device_put(
            params_stacked, NamedSharding(mesh, P(axis)))
        x_rep = jax.device_put(x_micro, NamedSharding(mesh, P()))
        out = jax.jit(mapped)(params_sharded, x_rep)
    # the schedule is S+M-1 ppermute ticks PLUS the final psum that
    # broadcasts the last stage's outputs ring-wide — record both kinds
    # or a hang post-mortem would misattribute a stall in the psum
    # (audit-trail gap caught by analysis/graphcheck collective
    # extraction; see tests/test_analysis.py)
    record_collective("collective-permute", "parallel.pipeline_apply",
                      bytes=hop_bytes)
    record_collective("all-reduce", "parallel.pipeline_apply output psum",
                      bytes=act_bytes)
    from ..telemetry import perf as _perf
    _perf.maybe_attribute_fn(mapped, (params_sharded, x_rep),
                             "pipeline_apply", n_devices=S, mesh=mesh)
    return out


class PipelineRunner:
    """Convenience wrapper: homogeneous stages (e.g. stacked transformer
    layers) with stacked parameters, trainable end to end."""

    def __init__(self, stage_fn, num_stages, mesh, axis="pp"):
        from .placement import as_mesh
        self.stage_fn = stage_fn
        self.num_stages = num_stages
        self.mesh = as_mesh(mesh)
        self.axis = axis

    def forward(self, params_stacked, x_micro):
        return pipeline_apply(self.stage_fn, self.num_stages, self.mesh,
                              self.axis, params_stacked, x_micro)

    def loss_and_grad(self, loss_fn, params_stacked, x_micro, y_micro):
        """loss_fn(pred, target) -> scalar; grads w.r.t. stacked params
        differentiate straight through the pipeline schedule."""

        def total_loss(params):
            preds = pipeline_apply(self.stage_fn, self.num_stages, self.mesh,
                                   self.axis, params, x_micro)
            return loss_fn(preds, y_micro)

        return jax.value_and_grad(total_loss)(params_stacked)
