"""The grouped product's tiling rule (``ops/pallas_kernels._gmm_fwd_tiling``)
at every configuration's real shapes, the record of what each traced product
took (``grouped_matmul_tilings``), and the two kernels that run the
weight-resident tiling, megablox's and ``_gmm_group_ahead``, in the Pallas
interpreter against ``jax.lax.ragged_dot``.

A product whose groups own fewer rows than ``_GMM_MIN_ROWS`` on average is
bound by reading its weights: it takes the whole contracted axis in one tile,
so that consecutive visits to a group keep its weight block, and the
group-ahead kernel, which copies the next group's weights while the current
group's visits run.  Anything else keeps ``_gmm_tiling``'s and megablox, and
so does every backward product."""
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def _lfm2_products():
    """The hybrid serving step's row budgets, as its Decoder derives them,
    with its (k, n) pairs: gate and up 2,048 x 1,792, down 1,792 x 2,048."""
    import jax.numpy as jnp
    from mxnet_tpu.models import lfm2_moe
    cfg, traffic = _config("lfm2-8b-a1b"), _traffic("serve-closed128-4k")
    dec = lfm2_moe.Decoder(cfg, num_layers=cfg["num_hidden_layers"],
                           vocab_size=cfg["vocab_size"],
                           slots=traffic["slots"],
                           chunk_rows=traffic["prefill_tokens_per_step"],
                           dtype=jnp.bfloat16)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return cfg["num_experts"], dec.buckets, d, f


def _sarvam_products():
    import jax.numpy as jnp
    from mxnet_tpu.models import sarvam_mla
    cfg, traffic = _config("sarvam-105b"), _traffic("serve-closed64-4k")
    dec = sarvam_mla.Decoder(cfg, num_layers=cfg["num_hidden_layers"],
                             vocab_size=cfg["vocab_size"],
                             slots=traffic["slots"],
                             chunk_rows=traffic["prefill_tokens_per_step"],
                             dtype=jnp.bfloat16)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return cfg["num_experts"], dec.buckets, d, f


def _trinity_products():
    """The training step's row budgets (``moe_ffn_held``'s default) for one
    sequence of the traffic's length."""
    from mxnet_tpu.parallel.moe import row_buckets
    cfg, traffic = _config("trinity-mini"), _traffic("train-b1x8k")
    buckets = row_buckets(traffic["batch"] * traffic["seq_len"],
                          cfg["num_experts_per_tok"], cfg["num_experts"],
                          cfg["router_width"])
    return (cfg["num_experts"], buckets, cfg["hidden_size"],
            cfg["moe_intermediate_size"])


# name -> (products, the forward tilings of (gate/up, down), weight-resident)
CASES = {
    "lfm2-8b-a1b": (_lfm2_products,
                    ((128, 2048, 896), (128, 1792, 1024)), True),
    "sarvam-105b": (_sarvam_products,
                    ((128, 4096, 512), (128, 2048, 1024)), True),
    "trinity-mini": (_trinity_products,
                     ((512, 1024, 1024), (512, 1024, 1024)), False),
}


def _tilings(case):
    """[(m, groups, k, n, out dtype, tiling, resident, which)] of every
    forward product of every row budget of the case; ``which`` 0 for the
    gate and up products, 1 for down."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    products, _want, _resident = CASES[case]
    groups, buckets, d, f = products()
    out = []
    for m in buckets:
        for which, (k, n, out_dtype) in enumerate(
                ((d, f, jnp.bfloat16), (f, d, jnp.float32))):
            tiling, resident = pk._gmm_fwd_tiling(m, groups, k, n,
                                                  jnp.bfloat16, out_dtype)
            out.append((m, groups, k, n, out_dtype, tiling, resident, which))
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_rule_at_each_configurations_shapes(case, monkeypatch):
    """The serving steps' products are weight-bound and take the whole
    contracted axis (gate/up 896 columns at k 2,048, down 1,024 at 1,792;
    sarvam's 512 at k 4,096) and the group-ahead kernel; trinity's training
    products (640 rows an expert and more) keep exactly ``_gmm_tiling``'s
    MXU-bound (512, 1024, 1024) and megablox, forward and backward; every
    backward product, at any shape, keeps megablox."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    _products, want, resident = CASES[case]
    got = _tilings(case)
    assert got
    for m, groups, k, n, _out, tiling, took, which in got:
        assert took is resident, (m, k, n)
        assert tiling == want[which], (m, k, n, tiling)
        if not resident:
            assert tiling == pk._gmm_tiling(m, groups)

    monkeypatch.setattr(pk, "_GMM_TILINGS", {})
    S = jax.ShapeDtypeStruct
    for m, groups, k, n, out_dtype, *_ in got:
        def loss(x, w, out_dtype=out_dtype, groups=groups):
            return pk._gmm_tpu(x, w, jnp.zeros((groups,), jnp.int32),
                               out_dtype).astype(jnp.float32).sum()
        jax.eval_shape(jax.grad(loss, argnums=(0, 1)),
                       S((m, k), jnp.bfloat16),
                       S((groups, k, n), jnp.bfloat16))
    records = pk.grouped_matmul_tilings()
    assert {r["pass"] for r in records} == {"forward", "backward"}
    assert len(records) == 2 * len({(m, k, n) for m, _g, k, n, *_ in got})
    for r in records:
        ahead = r["pass"] == "forward" and resident
        assert r["prefetch"] == ("group" if ahead else "megablox"), r


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_tile_divides_its_axis_and_fits_vmem(case):
    """Rows and columns divide their axes (or the contracted tile is the
    whole axis), and a weight-resident tile's buffers fit the budgets."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    for m, _g, k, n, out_dtype, (tm, tk, tn), resident, _w in _tilings(case):
        assert m % tm == 0 and n % tn == 0
        assert tk == k if resident else k % tk == 0
        if resident:
            assert tn % 128 == 0
            assert 2 * k * tn * 2 <= pk._GMM_WEIGHT_VMEM
            assert pk._gmm_vmem(tm, k, tn, 2, jnp.dtype(out_dtype).itemsize) \
                <= pk._GMM_SCOPED_VMEM


def test_the_rule_reads_shapes_alone():
    """Mean rows at the break-even or over it keep ``_gmm_tiling``; a column
    axis with no multiple of 128 that divides it, or a contracted axis too
    long for one 128-column tile, keeps it too; a longer contracted axis
    narrows the column tile."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    bf = jnp.bfloat16
    assert pk._gmm_fwd_tiling(4096, 16, 2048, 1024, bf, bf) \
        == ((512, 1024, 1024), False)
    assert pk._gmm_fwd_tiling(2048, 32, 2048, 1000, bf, bf) \
        == (pk._gmm_tiling(2048, 32), False)
    assert pk._gmm_fwd_tiling(2048, 32, 65536, 1024, bf, bf)[1] is False
    assert pk._gmm_fwd_tiling(256, 8, 256, 384, bf, bf) \
        == ((128, 256, 384), True)
    assert pk._gmm_fwd_tiling(256, 8, 8192, 384, bf, bf) \
        == ((128, 8192, 128), True)
    assert pk._gmm_fwd_tiling(96, 8, 256, 384, bf, bf) == ((32, 256, 384),
                                                          True)


def test_traced_products_are_recorded(monkeypatch):
    """Tracing the TPU path records each product once by shape, forward and
    backward, with the tiling it took, whether it was weight-resident and
    the pipeline that brought its weights."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_GMM_TILINGS", {})
    S = jax.ShapeDtypeStruct

    def forward(x, w, sizes):
        return pk._gmm_tpu(x, w, sizes, jnp.bfloat16)
    for _ in range(2):
        jax.eval_shape(forward, S((2048, 2048), jnp.bfloat16),
                       S((32, 2048, 1792), jnp.bfloat16),
                       S((32,), jnp.int32))

    def loss(x, w):
        return pk._gmm_tpu(x, w, jnp.zeros((16,), jnp.int32),
                           jnp.bfloat16).astype(jnp.float32).sum()
    jax.eval_shape(jax.grad(loss, argnums=(0, 1)),
                   S((10240, 2048), jnp.bfloat16),
                   S((16, 2048, 1024), jnp.bfloat16))
    got = pk.grouped_matmul_tilings()
    assert got == [
        {"pass": "forward", "m": 2048, "groups": 32, "k": 2048, "n": 1792,
         "dtype": "bfloat16", "tiling": (128, 2048, 896),
         "weight_resident": True, "prefetch": "group"},
        {"pass": "forward", "m": 10240, "groups": 16, "k": 2048, "n": 1024,
         "dtype": "bfloat16", "tiling": (512, 1024, 1024),
         "weight_resident": False, "prefetch": "megablox"},
        {"pass": "backward", "m": 10240, "groups": 16, "k": 2048, "n": 1024,
         "dtype": "bfloat16", "tiling": (512, 1024, 1024),
         "weight_resident": False, "prefetch": "megablox"}]


# (rows, sizes of 8 groups): skewed with empty groups and a group across the
# 128-row boundary, rows past the last group; even; one group; a group across
# three row tiles with the last group empty; empty groups at the end of one
# column tile and at the start of the next; the last group empty
SIZES = {"skewed": (256, [100, 3, 0, 60, 1, 0, 40, 20]),
         "even": (256, [32] * 8),
         "one_group": (256, [0, 0, 0, 256, 0, 0, 0, 0]),
         "three_tiles": (512, [10, 300, 20, 0, 40, 0, 30, 0]),
         "empty_edges": (256, [0, 0, 50, 100, 60, 30, 0, 0]),
         "last_empty": (256, [40, 30, 50, 20, 60, 30, 26, 0])}


@pytest.mark.parametrize("kernel", ["megablox", "group_ahead"])
@pytest.mark.parametrize("k", [256, 8192])
@pytest.mark.parametrize("pattern", sorted(SIZES))
def test_weight_resident_kernel_equals_ragged_dot(pattern, k, kernel):
    """Each kernel in the interpreter, one contracted tile (and, at k 8,192,
    three 128-column tiles of n 384), equals ``ragged_dot`` on every row
    once the rows past the last group are masked as ``_held_rows`` masks
    them.  The group-ahead kernel runs in the TPU interpreter, where a copy
    lands only when it is waited on and memory no copy reached reads NaN,
    and equals megablox's to the bit."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from mxnet_tpu.ops import pallas_kernels as pk
    m, sizes = SIZES[pattern]
    groups, n = 8, 384
    rs = np.random.default_rng(k + len(pattern))
    x = jnp.asarray(rs.normal(size=(m, k)), jnp.bfloat16)
    w = jnp.asarray(rs.normal(size=(groups, k, n)) / np.sqrt(k), jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    tiling, resident = pk._gmm_fwd_tiling(m, groups, k, n, jnp.bfloat16,
                                          jnp.float32)
    assert resident and tiling[1] == k and n % tiling[2] == 0
    assert (tiling[2] < n) == (k == 8192)
    with jax.enable_x64(False):
        got = pk._megablox().gmm(x, w, sizes, jnp.float32, tiling,
                                 interpret=True)
        if kernel == "group_ahead":
            megablox, got = got, pk._gmm_group_ahead(
                x, w, sizes, jnp.float32, tiling,
                interpret=pltpu.InterpretParams())
    want = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
    live = (jnp.arange(m) < jnp.sum(sizes))[:, None]
    got, want = (np.asarray(jnp.where(live, a, 0)) for a in (got, want))
    assert np.abs(got - want).max() <= 1e-4 * max(1.0, np.abs(want).max())
    if kernel == "group_ahead":
        np.testing.assert_array_equal(
            got, np.asarray(jnp.where(live, megablox, 0)))
