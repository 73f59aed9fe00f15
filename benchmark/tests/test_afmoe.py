"""CPU tests of what PR 33 added for the AFMoE decoder, at a small size
(hidden 64, 4 query heads on 2 key/value heads of 16, window 8, T 32, 8
experts scored, 2 a token, 4 held, one dense layer + one period): the
windowed grouped-query flash kernels, the drop-free expert layer and its
share of a deployment, the program against ``refs/afmoe.py``, and the tiny
cell beside this file (``tiny_afmoe/``) sound and with each fault planted.
``python -m pytest benchmark/tests/test_afmoe.py -q``; tier-1 collects it
through ``tests/test_benchmark_afmoe.py``.
"""
import io
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import afmoe_counts, harness  # noqa: E402
from benchmark.tests.test_benchmark import FAULTS, TEST_PEAKS  # noqa: E402

TINY = os.path.join(HERE, "tiny_afmoe", "BENCHMARK.json")
CELL = "tiny.afmoe"


@pytest.fixture(scope="module")
def cpu_device():
    import mxnet_tpu  # noqa: F401
    import jax
    return jax.devices()[:1]


def _cell_parts(cpu_device, seed=5, **training):
    from benchmark import run
    _b, cell, files, data = run.load_cell(TINY, CELL)
    data["cfg"]["training"].update(training)
    ctx = run.Context(files=files, seed=seed, devices=cpu_device,
                      spans=harness.Spans(), root=ROOT, cell=cell, **data)
    return ctx, files.module("drivers", "train")


def _ref():
    return harness.load_module(os.path.join(ROOT, "benchmark", "refs",
                                            "afmoe.py"))


def _tiny_cfg():
    return harness.load_json(os.path.join(HERE, "tiny_afmoe", "bench",
                                          "configs", "tiny-afmoe.json"))


# -- the windowed grouped-query flash kernels against the einsum path ----------

def _attention_op(q, k, v, window, flash):
    from mxnet_tpu.ops.registry import get_op
    op = get_op("_contrib_fused_attention")
    attrs = op.parse_attrs(dict(causal=True, window=window, block_q=8,
                                flash_min_seq=1 if flash else 10 ** 6))
    return op.fn(attrs, q, k, v)


@pytest.mark.parametrize("T,window,heads,kv_heads,sub_k", [
    (32, 8, 4, 2, 8),       # the tiny cell's layer: window = the q block
    (32, 5, 4, 1, 8),       # a window no block divides; one key/value head
    (32, 40, 4, 2, 8),      # T < window: the band is the whole triangle
    (64, 24, 2, 2, 16),     # groups of one, sub-tiles inside a key block
    (64, 7, 6, 3, 16),      # a window inside one sub-tile
    (32, 0, 4, 2, 8),       # grouped heads, no window
])
def test_windowed_grouped_flash_equals_einsum(cpu_device, monkeypatch, T,
                                              window, heads, kv_heads, sub_k):
    """Forward and all three gradients, through the operator: the Pallas
    interpreter runs the kernels' own code (sub-tile walk, dead-block
    skipping, dK/dV summed over a group in the scratch)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu.ops.pallas_kernels as pk
    from mxnet_tpu.ops import autotune
    monkeypatch.setattr(pk, "_FLASH_SUB_K", sub_k)
    monkeypatch.setattr(autotune, "flash_blocks",
                        lambda *a, **kw: (8, 2 * sub_k))
    rs = np.random.default_rng(T + window)
    q, k, v, do = (jnp.asarray(rs.normal(size=(2, T, h, 16)), jnp.float32)
                   for h in (heads, kv_heads, kv_heads, heads))
    outs = {}
    for flash in (True, False):
        out, vjp = jax.vjp(
            lambda *qkv: _attention_op(*qkv, window, flash), q, k, v)
        outs[flash] = (out,) + vjp(do)
    assert outs[True][2].shape == k.shape       # summed over the group
    for got, want in zip(outs[True], outs[False]):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_a_window_needs_causal(cpu_device):
    import jax.numpy as jnp
    import mxnet_tpu.ops.pallas_kernels as pk
    x = jnp.zeros((1, 16, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        pk.fused_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match="head count"):
        pk.fused_attention(jnp.zeros((1, 16, 3, 16)), x, x, causal=True)


# -- the expert layer -----------------------------------------------------------

E, HELD, TOP_K, D_MODEL, WIDTH, TOKENS = 8, 4, 2, 16, 8, 32


def _layer_weights(seed=0, held=HELD):
    import jax.numpy as jnp
    rs = np.random.default_rng(seed)

    def f32(*shape):
        return jnp.asarray(rs.normal(size=shape) * 0.5, jnp.float32)

    return {"m": f32(TOKENS, D_MODEL), "wr": f32(D_MODEL, E),
            "shared": (f32(D_MODEL, WIDTH), f32(D_MODEL, WIDTH),
                       f32(WIDTH, D_MODEL)),
            "experts": (f32(held, D_MODEL, WIDTH), f32(held, D_MODEL, WIDTH),
                        f32(held, WIDTH, D_MODEL))}


def _per_token_loop(m, wr, bias, shared, experts, first, scale=2.826):
    """The layer by its definition: every token through each of its picks
    that is held, one at a time; nothing sorted, nothing grouped."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(m @ wr)
    _, picks = jax.lax.top_k(s + bias, TOP_K)
    w = jnp.take_along_axis(s, picks, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    w1, w3, w2 = experts
    rows = []
    for t in range(m.shape[0]):
        row = jnp.zeros_like(m[t])
        for j in range(TOP_K):
            e = int(picks[t, j]) - first
            if 0 <= e < w1.shape[0]:
                row = row + w[t, j] * (
                    (jax.nn.silu(m[t] @ w1[e]) * (m[t] @ w3[e])) @ w2[e])
        rows.append(row)
    s1, s3, s2 = shared
    return jnp.stack(rows) + (jax.nn.silu(m @ s1) * (m @ s3)) @ s2


@pytest.mark.parametrize("routing,bias_at,first,buckets,live_rows", [
    ("uniform", {}, 0, None, None),
    ("uniform, the other half held", {}, 4, (16, 40, 64), None),
    # every token's first pick on held expert 1, its second not held: the
    # 32 live rows pass the first budget and take the second
    ("all on one held expert", {1: 10.0, 6: 9.0}, 0, (8, 40, 64), 32),
    # both picks of every token held: the worst case the buffers admit
    ("every pick held", {1: 10.0, 2: 9.0}, 0, (8, 40, 64), 64),
])
def test_expert_layer_equals_per_token_loop(cpu_device, routing, bias_at,
                                            first, buckets, live_rows):
    """No capacity and no drop under any routing, forward and gradients."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe
    w = _layer_weights()
    bias = jnp.zeros(E, jnp.float32)
    for e, b in bias_at.items():
        bias = bias.at[e].set(b)

    def layer(m, wr, shared, experts):
        return moe.moe_ffn_held(m, wr, bias, shared, experts, num_experts=E,
                                first_expert=first, top_k=TOP_K,
                                route_scale=2.826, buckets=buckets)

    def loop(m, wr, shared, experts):
        return _per_token_loop(m, wr, bias, shared, experts, first)

    args = (w["m"], w["wr"], w["shared"], w["experts"])
    out, load = layer(*args)
    np.testing.assert_allclose(out, loop(*args), atol=1e-5, rtol=1e-5)
    assert float(load.sum()) == TOKENS * TOP_K
    if live_rows is not None:
        assert float(load[first:first + HELD].sum()) == live_rows
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(layer(*a)[0])),
                   argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(loop(*a))),
                    argnums=(0, 1, 2, 3))(*args)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, r, atol=2e-5, rtol=2e-5)


def test_row_budgets_admit_every_pick():
    from mxnet_tpu.parallel import moe
    # the benchmark's layer: 8,192 expected rows, 65,536 at the worst
    assert moe.row_buckets(8192, 8, 16, 128) == (10240, 20480, 40960, 65536)
    assert moe.row_buckets(32, 2, 4, 8) == (40, 64)
    assert moe.row_buckets(32, 2, 8, 8)[-1] == 64
    with pytest.raises(ValueError, match="nothing is dropped"):
        import jax.numpy as jnp
        w = _layer_weights()
        moe.moe_ffn_held(w["m"], w["wr"], jnp.zeros(E), w["shared"],
                         w["experts"], num_experts=E, first_expert=0,
                         top_k=TOP_K, buckets=(8, 40))


def test_the_shares_add_up_to_the_whole_layer(cpu_device):
    """The parts that the shares of all chips give (two chips of four
    experts each), the shared expert counted once, equal what the uncut
    reference gives for the whole layer of eight."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe
    ref = _ref()
    w = _layer_weights(seed=3, held=E)
    bias = jnp.asarray(np.random.default_rng(4).normal(size=E) * 0.1,
                       jnp.float32)
    zero_shared = tuple(jnp.zeros_like(s) for s in w["shared"])
    total = 0.0
    for chip, first in enumerate((0, HELD)):
        mine = tuple(x[first:first + HELD] for x in w["experts"])
        out, _load = moe.moe_ffn_held(
            w["m"], w["wr"], bias, w["shared"] if chip == 0 else zero_shared,
            mine, num_experts=E, first_expert=first, top_k=TOP_K,
            route_scale=2.826)
        total = total + out
    cfg = dict(_tiny_cfg(), num_experts=E, router_width=E, first_expert=0,
               hidden_size=D_MODEL, moe_intermediate_size=WIDTH)
    p = {"router_weight": w["wr"]}
    p.update(zip(("shared_w1", "shared_w3", "shared_w2"), w["shared"]))
    p.update(zip(("expert_w1", "expert_w3", "expert_w2"), w["experts"]))

    def mm(x, y):
        return jnp.matmul(x, y, precision=ref.HIGHEST)

    whole, load = ref._experts(p, "", w["m"], bias, cfg, mm)
    np.testing.assert_allclose(total, whole, atol=1e-5, rtol=1e-5)
    assert float(load.sum()) == TOKENS * TOP_K


def test_bias_update_and_load_are_the_operators_state(cpu_device):
    """A training forward writes the step's load (all experts) and moves
    the bias against it; a predicting one leaves both."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.parallel import moe
    op = get_op("_contrib_moe_ffn")
    assert op.aux_inputs == (8, 9) and op.writeback == {8: 1, 9: 2}
    w = _layer_weights(seed=6)
    bias = jnp.zeros(E, jnp.float32).at[3].set(0.5)
    stale = jnp.full(E, 7.0, jnp.float32)
    attrs = op.parse_attrs(dict(num_experts=E, experts_held=HELD, top_k=TOP_K,
                                num_hidden=WIDTH, route_scale=2.826))
    x = w["m"].reshape(2, TOKENS // 2, D_MODEL)
    args = (x, w["wr"]) + w["shared"] + w["experts"] + (bias, stale)
    train = op.fn(type(attrs)(attrs, _train=True), *args)
    picks, _c = moe.route_top_k(w["m"], w["wr"], bias, TOP_K)
    load = np.bincount(np.asarray(picks).ravel(), minlength=E)
    np.testing.assert_array_equal(train[2], load)
    assert load.sum() == TOKENS * TOP_K and load.max() > load.min()
    np.testing.assert_allclose(
        train[1], np.asarray(bias) + 0.001 * np.sign(load.mean() - load),
        atol=1e-7)
    assert train[0].shape == x.shape
    predict = op.fn(type(attrs)(attrs, _train=False), *args)
    np.testing.assert_array_equal(predict[1], bias)
    np.testing.assert_array_equal(predict[2], stale)
    np.testing.assert_array_equal(predict[0], train[0])


# -- the program against the reference ------------------------------------------

def test_program_in_float32_follows_the_reference(cpu_device):
    """The same mathematics: with the program's parameters and products in
    float32, its loss, first gradient and three-step change (biases updated
    between the steps on both sides) agree with the reference far inside
    what bfloat16 leaves."""
    ctx, train = _cell_parts(cpu_device, param_dtype="float32",
                             compute_dtype="float32")
    drv = train.Driver(ctx)
    drv.setup()
    assert [n for n in drv.aux_names if n.endswith("expert_bias")]
    assert all(not a.any() for a in drv.aux0)       # bias and load start at 0
    moved = [np.asarray(a) for n, a in zip(drv.aux_names, drv.aux)
             if n.endswith("expert_bias")]
    assert all(np.abs(b).max() == pytest.approx(0.003) for b in moved)
    prog = drv.program_readings()
    drv.release()
    ref = drv.reference_readings()
    assert train.loss_gap(prog, ref) < 1e-5
    every = dict.fromkeys(train.NUMBERS, 0)
    for name, value, _lim, where in train.checks(prog, ref, every):
        assert value < 2e-3, (name, value, where)


@pytest.fixture(scope="module")
def runs(cpu_device):
    from benchmark import run

    def execute(fault=None, trace_on=False):
        err = io.StringIO()
        result = run.execute(TINY, CELL, 2**31 + 11, 0.4, trace_on,
                             cpu_device, driver_class=FAULTS.get(fault),
                             err=err, peaks_for_tests=TEST_PEAKS)
        return result, err.getvalue()
    return execute


def test_sound_run_of_the_tiny_cell_is_correct(runs):
    result, err = runs(trace_on=True)
    assert result["correct"] is True, err
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["mfu_pct.train"]["value"] > 0
    assert result["metrics"]["trainer_enqueue_ms"]["value"] > 0
    # no device plane on the CPU: the scope readers find nothing and the
    # metrics are left out, as on a parent commit without the operator
    for name in ("moe_device_pct", "moe_route_device_pct", "moe_roofline",
                 "flash_roofline"):
        assert name not in result["metrics"]
    assert set(result["compared"]) == {"grad_norm_gap", "change_norm_gap",
                                       "compiled_in_window"}


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch"])
def test_planted_fault_in_the_tiny_cell_is_not_correct(runs, fault):
    result, err = runs(fault=fault)
    assert result["correct"] is False, err


def test_float8_control_fails_at_test_size(cpu_device):
    import jax.numpy as jnp
    ctx, train = _cell_parts(cpu_device)
    drv = train.Driver(ctx)
    drv.batches = drv.adapter.train_batches(ctx.cfg, ctx.traffic, 5)
    drv.store_dtypes = {k: (np.float32 if k.endswith("gamma")
                            else jnp.bfloat16)
                        for k in drv.ref.param_shapes(ctx.cfg)}
    ref = drv.reference_readings()
    ctrl = drv.reference_readings(cast=jnp.float8_e4m3fn)
    checked = train.checks(ctrl, ref, ctx.limits)
    assert any(v > lim for _n, v, lim, _w in checked), checked
    # half of a batch of one is no batch: the reference reads it as a step
    # that moves nothing, not as an error (calibrate.py plants it)
    empty = {k: v[:0] for k, v in drv.batches[0].items()}
    still = drv.ref.train_reference(ctx.cfg, 5, drv.store_dtypes, [empty],
                                    ctx.traffic)
    assert set(still["grad_norms"].values()) == {0.0}


def test_reference_stays_float32_under_x64(cpu_device):
    import jax
    import jax.numpy as jnp
    assert jax.config.jax_enable_x64
    ref, cfg = _ref(), _tiny_cfg()
    w = ref.make_weights(cfg, 2**31 + 3)
    assert set(w) == set(ref.param_shapes(cfg))
    assert {v.dtype for v in w.values()} == {jnp.dtype("float32")}
    x = np.zeros((1, 32), np.int32)
    for cast in (None, jnp.float8_e4m3fn):
        (ce, probs), g = jax.value_and_grad(
            lambda p: ref.summed_loss(p, x, x, cfg, cast=cast),
            has_aux=True)(w)
        assert ce.dtype == probs.dtype == jnp.float32
        assert {v.dtype for v in g.values()} == {jnp.dtype("float32")}


@pytest.mark.parametrize("window", [0, 8, 24])
def test_reference_attention_in_blocks_equals_whole(cpu_device, monkeypatch,
                                                    window):
    """The reference walks the queries a block at a time against a stretch
    of keys that moves with the block: the same as every query against
    every key under the mask."""
    import jax
    import jax.numpy as jnp
    ref = _ref()
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    rs = np.random.default_rng(window)
    T, H, G, D = 64, 4, 2, 16
    q, k, v = (jnp.asarray(rs.normal(size=(T, h, D)), jnp.float32)
               for h in (H, G, G))
    got = ref._attention(q, k, v, window, lambda x: x)
    s = jnp.einsum("qgrd,kgd->grqk", q.reshape(T, G, H // G, D), k,
                   precision=ref.HIGHEST) / 4.0
    gap = np.arange(T)[:, None] - np.arange(T)[None, :]
    seen = (gap >= 0) & ((gap < window) if window else True)
    want = jnp.einsum("grqk,kgd->qgrd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v,
                      precision=ref.HIGHEST).reshape(T, H, D)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


def test_reference_names_are_the_programs(cpu_device):
    from mxnet_tpu.models import afmoe
    ref, cfg = _ref(), _tiny_cfg()
    sym = afmoe.get_symbol(cfg)
    args, _out, aux = sym.infer_shape(data=(2, 32), softmax_label=(2, 32))
    got = {n: tuple(s) for n, s in zip(sym.list_arguments(), args)
           if n not in ("data", "softmax_label")}
    assert got == ref.param_shapes(cfg)
    aux = dict(zip(sym.list_auxiliary_states(), aux))
    for name, shape in ref.aux_shapes(cfg).items():
        assert tuple(aux[name]) == shape
        assert tuple(aux[name.replace("bias", "load")]) == shape


# -- the new counts against a hand count ----------------------------------------

def test_trinity_mini_counts_by_hand():
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "trinity-mini.json"))
    T = 8192
    assert afmoe_counts.attention_pairs(T) == T * (T + 1) // 2 == 33_558_528
    assert afmoe_counts.attention_pairs(T, 2048) \
        == 2048 * 2049 // 2 + (T - 2048) * 2048 == 14_681_088
    assert afmoe_counts.attention_pairs(1024, 2048) == 1024 * 1025 // 2
    assert afmoe_counts.layer_pairs(cfg, T) == [14_681_088] * 4 + [33_558_528]
    assert afmoe_counts.routed_rows(cfg, T) == T * 8 * 16 / 128 == 8192
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert attention == 27_262_976
    per_token = (5 * attention + 3 * 2048 * 6144
                 + 4 * (2048 * 128 + 2 * 3 * 2048 * 1024) + 2048 * 25024)
    assert afmoe_counts.matrix_macs_per_token(cfg) == per_token == 276_692_992
    pairs = 4 * 14_681_088 + 33_558_528
    step = 3 * (2 * T * per_token + 4 * pairs * 4096)
    assert afmoe_counts.train_flops_per_step(cfg, 1, T) == step
    assert 18.0e12 < step < 18.3e12
    f, b = afmoe_counts.flash_train_flops_bytes(cfg, 1, T)
    assert f == 12 * pairs * 4096 and b == 5 * T * 6 * (4096 + 512) * 2
    f, b = afmoe_counts.moe_train_flops_bytes(cfg, 1, T)
    assert f == 4 * 9 * 2 * 8192 * 2048 * 1024
    assert b == 4 * 9 * (8192 * 3072 + 16 * 2048 * 1024) * 2


def test_configuration_keeps_the_published_widths():
    """Every number of the source's config.json stands in the file under
    its key, but for the five the cut changes."""
    import json
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "trinity-mini.json"))
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "num_dense_layers", "layer_types",
         "num_experts", "vocab_size"])
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "intermediate_size": 6144, "moe_intermediate_size": 1024,
                 "num_experts_per_tok": 8, "sliding_window": 2048,
                 "router_width": 128, "num_shared_experts": 1,
                 "route_scale": 2.826, "rope_theta": 10000,
                 "rms_norm_eps": 1e-5, "load_balance_coeff": 0.001}
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"]["num_experts"] == 128
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert len(json.dumps(cfg["deployment"])) > 0 and cfg["assumed"]
