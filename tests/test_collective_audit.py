"""Collective-traffic accounting (parallel/audit.py): the dp gradient
all-reduce payload extracted from compiled HLO must match the analytic
model (sum of f32 grad bytes) — the quantitative basis of the scaling
story (BASELINE north star; reference measured ~90% linear at 256 GPUs
with the same ring-allreduce cost model)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel.audit import (collective_accounting,
                                      grad_payload_bytes,
                                      ring_allreduce_wire_bytes)
from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
from mxnet_tpu.parallel.trainer import ShardedTrainer


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip("needs %d devices" % n)


def test_dp_allreduce_payload_matches_grad_bytes():
    _need_devices(4)
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    spec = MeshSpec(make_mesh((4,), ("dp",)))
    tr = ShardedTrainer(net, spec, lr=0.1, momentum=0.9, wd=0.0)
    shapes = {"data": (8, 16), "softmax_label": (8,)}
    params, mom, aux = tr.init_state(shapes)
    feed = {"data": jax.device_put(np.zeros((8, 16), np.float32),
                                   spec.batch_sharding()),
            "softmax_label": jax.device_put(np.zeros((8,), np.float32),
                                            spec.batch_sharding())}
    jitted = tr._build_step(donate=False)
    txt = jitted.lower(params, mom, aux, feed, tr._keys(),
                       tr._guard_arrays()).compile().as_text()

    acct = collective_accounting(txt)
    assert "all-reduce" in acct, sorted(acct)
    measured = acct["all-reduce"]["bytes"]
    model = grad_payload_bytes(params)
    # XLA may fold the loss scalar or small aux reductions in; the grad
    # payload must dominate and match within 10%
    assert model > 0
    assert abs(measured - model) / model < 0.10, (measured, model)


def test_ring_wire_model():
    assert ring_allreduce_wire_bytes(1000, 8) == 2 * 7 * 1000 // 8
    assert ring_allreduce_wire_bytes(1000, 1) == 0


_FUSED_RS_HLO = """
ENTRY %main (p0: f32[64,32]) -> f32[8,32] {
  %p0 = f32[64,32]{1,0} parameter(0)
  %all-reduce = f32[64,32]{1,0} all-reduce(f32[64,32]{1,0} %p0), replica_groups=[1,8]<=[8], use_global_device_ids=true, to_apply=%add.clone
  %partition-id = u32[] partition-id()
  %convert = s32[] convert(u32[] %partition-id)
  %multiply = s32[] multiply(s32[] %convert, s32[] %c8)
  ROOT %dynamic-slice = f32[8,32]{1,0} dynamic-slice(f32[64,32]{1,0} %all-reduce, s32[] %multiply, s32[] %c0), dynamic_slice_sizes={8,32}
}
"""

def test_allreduce_then_partition_slice_is_counted_as_allreduce():
    """An all-reduce whose only consumer slices out this partition's shard
    still moves the full all-reduce on the wire: neither installed
    compiler folds the pair into a reduce-scatter, so the accounting
    reports the opcode that was emitted."""
    acct = collective_accounting(_FUSED_RS_HLO)
    assert "reduce-scatter" not in acct
    assert acct["all-reduce"] == {"count": 1, "bytes": 64 * 32 * 4}


def test_replica_groups_parsing_both_syntaxes():
    from mxnet_tpu.parallel.audit import parse_replica_groups
    assert parse_replica_groups("replica_groups={{0,4},{1,5}}, x=y") == \
        [(0, 4), (1, 5)]
    assert parse_replica_groups("replica_groups=[1,8]<=[8]") == \
        [tuple(range(8))]
    # iota with reshape+transpose: [4,2]<=[2,4]T(1,0) pairs stride-4 ids
    assert parse_replica_groups("replica_groups=[4,2]<=[2,4]T(1,0)") == \
        [(0, 4), (1, 5), (2, 6), (3, 7)]
    assert parse_replica_groups("channel_id=1") is None


def test_by_axis_attribution_on_dp_tp_mesh():
    """Replica groups map back to the mesh axes they span: dp groups
    label 'dp', tp groups 'tp', whole-mesh 'dpxtp', ppermute rings via
    their source-target pairs."""
    _need_devices(4)
    from mxnet_tpu.parallel.audit import AxisLabeler
    mesh = MeshSpec(make_mesh((2, 2), ("dp", "tp")))  # ids [[0,1],[2,3]]
    lab = AxisLabeler(mesh)
    assert lab.label_groups([(0, 2), (1, 3)]) == "dp"
    assert lab.label_groups([(0, 1), (2, 3)]) == "tp"
    assert lab.label_groups([(0, 1, 2, 3)]) == "dpxtp"
    assert lab.label_groups([(0, 3)]) == "unmapped"
    assert lab.label_groups([(0,), (1,)]) == "self"
    assert lab.label_pairs([(0, 2), (2, 0)]) == "dp"
    assert lab.label_pairs([(0, 1), (1, 0), (2, 3), (3, 2)]) == "tp"
    # accounting end: synthetic module over this mesh
    hlo = "\n".join([
        "ENTRY %main (p0: f32[16]) -> f32[16] {",
        "  %ar1 = f32[16]{0} all-reduce(f32[16]{0} %p0), "
        "replica_groups={{0,2},{1,3}}",
        "  ROOT %ar2 = f32[16]{0} all-reduce(f32[16]{0} %ar1), "
        "replica_groups={{0,1},{2,3}}",
        "}"])
    acct = collective_accounting(hlo, mesh=mesh)
    assert acct["all-reduce"]["by_axis"]["dp"]["bytes"] == 64
    assert acct["all-reduce"]["by_axis"]["tp"]["bytes"] == 64


def test_collective_wire_models():
    from mxnet_tpu.parallel.audit import (collective_wire_bytes,
                                          zero_update_model_bytes)
    assert collective_wire_bytes("all-reduce", 1000, 8) == 2 * 7 * 1000 // 8
    # reduce-scatter payload is the output shard: (n-1) hops of it
    assert collective_wire_bytes("reduce-scatter", 125, 8) == 7 * 125
    # all-gather payload is the gathered result: (n-1)/n of it on wire
    assert collective_wire_bytes("all-gather", 1000, 8) == 7 * 1000 // 8
    assert collective_wire_bytes("collective-permute", 42, 8) == 42
    m = zero_update_model_bytes(8000, 30)
    assert m == {"all-reduce": 8030, "all-gather": 8000}


def test_async_start_counts_operand_shapes_only():
    """-start accounting (audit.py): all-gather/reduce-scatter are
    asymmetric — halving the (operand, result) tuple overstated the
    all-gather payload by (1+n)/2; the operand shapes alone are what the
    collective is fed."""
    hlo = "\n".join([
        "  %ag = (f32[4]{0}, f32[16]{0}) all-gather-start(f32[4]{0} %x), "
        "replica_groups={{0,1,2,3}}, dimensions={0}",
        "  %rs = (f32[16]{0}, f32[4]{0}) reduce-scatter-start(f32[16]{0} "
        "%y), replica_groups={{0,1,2,3}}",
        "  %ar = (f32[8]{0}, f32[8]{0}) all-reduce-start(f32[8]{0} %z), "
        "replica_groups={}",
        "  %done = f32[16]{0} all-gather-done(%ag)",
    ])
    acct = collective_accounting(hlo)
    assert acct["all-gather"]["bytes"] == 4 * 4      # operand, not result
    assert acct["reduce-scatter"]["bytes"] == 16 * 4
    # symmetric op: operand == result == old halved-tuple accounting
    assert acct["all-reduce"]["bytes"] == 8 * 4
    assert acct["all-gather"]["count"] == 1          # -done not re-counted
