"""ZeRO-style sharded weight update (reference analog: BIGARRAY sharding
across servers kvstore_dist.h:156 + server-side optimizer
kvstore_dist_server.h:187; SURVEY §5.8 and "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training" map both to
reduce-scatter + shard-local update + weight all-gather under GSPMD).

shard_optimizer_state=True (which now implies the sharded UPDATE unless
MXNET_TPU_ZERO=0) must (a) place momentum dp-sharded so per-chip
optimizer memory drops by the dp degree, (b) run the update math on the
shards and all-gather the new weights — the installed compilers keep the
gradient reduction a full all-reduce followed by a partition slice, and
the test says so — and (c) produce bit-comparable training numerics to
the replicated path, grad accumulation included.
"""
import jax
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.parallel import audit
from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
from mxnet_tpu.parallel.trainer import ShardedTrainer


def _mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, name="fc1", num_hidden=32)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc2", num_hidden=8)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _run(zero, steps=4, seed=5, grad_accum=1, **kw):
    spec = MeshSpec(make_mesh((8,), ("dp",)))
    trainer = ShardedTrainer(_mlp(), spec, lr=0.1, momentum=0.9, wd=1e-4,
                             shard_optimizer_state=zero,
                             grad_accum=grad_accum, **kw)
    shapes = {"data": (16, 12), "softmax_label": (16,)}
    params, mom, aux = trainer.init_state(shapes, seed=seed)
    rs = np.random.RandomState(2)
    for _ in range(steps):
        data = rs.rand(16, 12).astype(np.float32)
        label = rs.randint(0, 8, 16).astype(np.float32)
        params, mom, aux, loss = trainer.step(
            params, mom, aux, {"data": data, "softmax_label": label})
    return trainer, params, mom, float(loss)


def test_zero_matches_replicated():
    tr_z, p_z, m_z, loss_z = _run(zero=True)
    tr_r, p_r, m_r, loss_r = _run(zero=False)
    assert abs(loss_z - loss_r) < 1e-4
    for n, a, b in zip(tr_z.param_names, p_z, p_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=n)
    for n, a, b in zip(tr_z.param_names, m_z, m_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=n)


def test_zero_memory_drops_8x():
    """Per-device optimizer-state bytes must drop by the dp degree for
    every dp-divisible tensor."""
    tr, params, mom, _ = _run(zero=True, steps=1)
    by_name = dict(zip(tr.param_names, mom))
    m = by_name["fc1_weight"]             # (32, 12) momentum
    assert m.addressable_shards[0].data.shape == (4, 12)   # 32/8 rows
    m2 = by_name["fc1_bias"]              # (32,) momentum
    assert m2.addressable_shards[0].data.shape == (4,)
    # params stay replicated (ZeRO-1)
    p = dict(zip(tr.param_names, params))["fc1_weight"]
    assert p.addressable_shards[0].data.shape == (32, 12)

    # replicated control: full momentum everywhere
    tr_r, _, mom_r, _ = _run(zero=False, steps=1)
    mr = dict(zip(tr_r.param_names, mom_r))["fc1_weight"]
    assert mr.addressable_shards[0].data.shape == (32, 12)


def test_zero_composes_with_tp():
    """dp x tp mesh with ZeRO: momentum carries BOTH the tp sharding of
    its parameter and an extra dp-sharded dim."""
    spec = MeshSpec(make_mesh((2, 2), ("dp", "tp")))
    trainer = ShardedTrainer(_mlp(), spec, shard_optimizer_state=True)
    params, mom, aux = trainer.init_state(
        {"data": (8, 12), "softmax_label": (8,)})
    m = dict(zip(trainer.param_names, mom))["fc1_weight"]   # (32, 12)
    # tp shards dim0 (32→16), dp shards dim1 (12→6)
    assert m.addressable_shards[0].data.shape == (16, 6)
    p = dict(zip(trainer.param_names, params))["fc1_weight"]
    assert p.addressable_shards[0].data.shape == (16, 12)


def test_zero_grad_accum_parity():
    """ZeRO under gradient accumulation: the per-micro reduce-scatter +
    sharded f32 accumulator still match the replicated path bit-for-bit
    (up to fp roundoff) — the elastic-resize combination."""
    _, p_z, m_z, _ = _run(zero=True, grad_accum=2)
    tr, p_r, m_r, _ = _run(zero=False, grad_accum=2)
    for n, a, b in zip(tr.param_names, p_z, p_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=n)


def test_zero_hlo_allreduce_plus_weight_allgather():
    """The wire contract as the installed compiler emits it: with the
    sharded update ON, the compiled step all-reduces every gradient in
    full (each device then slices its shard; no reduce-scatter op is
    formed) and all-gathers the updated weights.  The audited bytes
    reconcile with the analytic ZeRO model."""
    tr, params, mom, _ = _run(zero=True, steps=1)
    feed = {"data": jax.device_put(np.zeros((16, 12), np.float32),
                                   tr.spec.batch_sharding()),
            "softmax_label": jax.device_put(np.zeros((16,), np.float32),
                                            tr.spec.batch_sharding())}
    jitted = tr._build_step(donate=False)
    txt = jitted.lower(params, mom, (), feed, tr._keys(),
                       tr._guard_arrays()).compile().as_text()
    acct = audit.collective_accounting(txt, mesh=tr.spec.mesh)
    shardable, residual = tr._zero_split_bytes()
    model = audit.zero_update_model_bytes(shardable, residual)
    assert "reduce-scatter" not in acct
    assert acct["all-gather"]["count"] >= 4              # one per param
    # payloads match the model on this bn-free MLP (the all-reduce also
    # carries the scalar loss)
    assert acct["all-gather"]["bytes"] == model["all-gather"]
    assert 0 <= acct["all-reduce"]["bytes"] - model["all-reduce"] <= 64
    # per-axis attribution: every byte is dp traffic on a pure-dp mesh
    assert set(acct["all-gather"]["by_axis"]) == {"dp"}

    # the replicated control still all-reduces the full grad payload
    tr_r, p_r, m_r, _ = _run(zero=False, steps=1)
    txt_r = tr_r._build_step(donate=False).lower(
        p_r, m_r, (), feed, tr_r._keys(),
        tr_r._guard_arrays()).compile().as_text()
    acct_r = audit.collective_accounting(txt_r)
    assert "reduce-scatter" not in acct_r
    full = audit.grad_payload_bytes(p_r)
    assert abs(acct_r["all-reduce"]["bytes"] - full) / full < 0.10


def test_mom_sharding_picks_largest_divisible_dim():
    """Conv-shaped optimizer state (out, in, kh, kw): the dp shard must
    ride the LARGEST free divisible dim — the old first-fit could pick a
    tiny out-channel (or kernel) dim and strand per-shard memory in tile
    padding."""
    spec = MeshSpec(make_mesh((4, 2), ("dp", "tp")))
    trainer = ShardedTrainer(_mlp(), spec, shard_optimizer_state=True)
    # free dims after tp takes dim0: (64, 4, 4) — first-fit would grab
    # nothing before 64 here, so ALSO check the pure first-fit trap:
    # dim0 (8) divides dp=4 but dim1 (64) is the right choice
    def spec_of(s):
        dims = tuple(s.spec) + (None,) * (4 - len(s.spec))
        return dims

    s = trainer.mom_sharding("conv_weight", (8, 64, 4, 4))
    assert spec_of(s) == ("tp", "dp", None, None), spec_of(s)
    spec_dp = MeshSpec(make_mesh((4,), ("dp",)))
    tr_dp = ShardedTrainer(_mlp(), spec_dp, shard_optimizer_state=True)
    s = tr_dp.mom_sharding("conv_weight", (8, 64, 4, 4))
    assert spec_of(s) == (None, "dp", None, None), spec_of(s)
    # ties break to the earliest dim; no divisible dim -> unsharded
    s = tr_dp.mom_sharding("conv_weight", (8, 8, 3, 3))
    assert spec_of(s) == ("dp", None, None, None), spec_of(s)
    s = tr_dp.mom_sharding("odd", (7, 5, 3, 3))
    assert spec_of(s) == (None, None, None, None), spec_of(s)


def test_zero_env_knob(monkeypatch):
    """MXNET_TPU_ZERO=0 reverts shard_optimizer_state to storage-only
    sharding; =1 arms the full update without any ctor flag; the ctor
    arg wins over the env."""
    spec = MeshSpec(make_mesh((8,), ("dp",)))
    monkeypatch.setenv("MXNET_TPU_ZERO", "0")
    tr = ShardedTrainer(_mlp(), spec, shard_optimizer_state=True)
    assert tr.shard_optimizer_state and not tr.shard_weight_update
    monkeypatch.setenv("MXNET_TPU_ZERO", "1")
    tr = ShardedTrainer(_mlp(), spec)
    assert tr.shard_optimizer_state and tr.shard_weight_update
    tr = ShardedTrainer(_mlp(), spec, zero=False)
    assert not tr.shard_weight_update
    monkeypatch.delenv("MXNET_TPU_ZERO")
    tr = ShardedTrainer(_mlp(), spec, shard_optimizer_state=True)
    assert tr.zero and tr.shard_weight_update    # follows the state flag
    # dp=1: storage/update sharding degrade to no-ops, never an error
    tr1 = ShardedTrainer(_mlp(), MeshSpec(make_mesh((1,), ("dp",))),
                         zero=True)
    assert not tr1.shard_weight_update
