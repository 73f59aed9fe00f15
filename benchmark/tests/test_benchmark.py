"""CPU tests of the benchmark's own code: ``python -m pytest benchmark/tests -q``.

The drivers run end to end at the tiny configuration beside this file
(``tiny/``), through ``run.execute`` - everything of a run but ``main()``'s
look for a chip - once sound and once with each fault planted underneath.
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import compare, flops, harness, trace, traffic  # noqa: E402

TINY = os.path.join(HERE, "tiny", "BENCHMARK.json")
TEST_PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


# -- the trace reducer on a small recorded trace ------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_events.json")) as f:
        doc = json.load(f)
    return [tuple(e) for e in doc["events"]], doc["expect"]


def test_union_of_overlapping_intervals():
    assert trace.union_seconds([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.union_seconds([]) == 0


def test_reducer_on_recorded_trace(recorded):
    events, expect = recorded
    assert trace.device_planes(events) == expect["planes"]
    assert trace.busy_seconds(events) == pytest.approx(expect["busy_s"],
                                                       rel=1e-9)
    for name, seconds in expect["seconds_by_name"].items():
        assert trace.seconds_by_name(events, name) == pytest.approx(
            seconds, rel=1e-9)
    assert trace.seconds_by_name(events, "no_such_kernel") is None
    top = trace.top_ops(events, 3)
    assert [t[0] for t in top] == expect["top3"]
    assert len(trace.idle_gaps(events)) <= 10


def test_wrappers_are_not_counted_twice():
    ev = [("/device:TPU:0", "XLA Ops", "while.3", 0.0, 100.0),
          ("/device:TPU:0", "XLA Ops", "fusion.1", 10.0, 20.0),
          ("/device:TPU:0", "XLA Modules", "jit_step", 0.0, 100.0),
          ("/host:CPU", "python", "enqueue", 40.0, 30.0),
          ("/device:TPU:0", "XLA Ops", "fusion.2", 80.0, 10.0)]
    assert trace.busy_seconds(ev) == pytest.approx(30e-9)
    assert trace.idle_gaps(ev) == [["enqueue", pytest.approx(50e-9)]]


def test_device_events_are_cut_to_the_traced_segment():
    """The profiler runs before and after the segment: an operation that
    straddles an edge counts by its part inside, one outside not at all."""
    ev = [("/host:CPU", "python", trace.WINDOW_SPAN, 100.0, 100.0),
          ("/device:TPU:0", "XLA Ops", "fusion.1", 50.0, 30.0),     # before
          ("/device:TPU:0", "XLA Ops", "decode_attn.2", 90.0, 30.0),  # 20 in
          ("/device:TPU:0", "XLA Ops", "fusion.3", 150.0, 10.0),    # inside
          ("/device:TPU:0", "XLA Ops", "decode_attn.4", 190.0, 40.0)]  # 10
    assert trace.window_of(ev) == (100.0, 200.0)
    cut = trace.clip_to_window(ev)
    assert trace.busy_seconds(cut) == pytest.approx(40e-9)
    assert trace.seconds_by_name(cut, "decode_attn") == pytest.approx(30e-9)
    assert trace.busy_seconds(ev) == pytest.approx(110e-9)
    no_span = ev[1:]
    assert trace.clip_to_window(no_span) == no_span


# -- the FLOP functions against a hand count -----------------------------------

def _cfg(name):
    return harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                          name + ".json"))


def test_gpt2_small_flops_by_hand():
    cfg = _cfg("gpt2-small")
    # per token: 12 layers x 2 x (4 x 768^2 + 2 x 768 x 3072) + head
    per_token = 12 * 2 * (4 * 768 * 768 + 2 * 768 * 3072) \
        + 2 * 768 * 50257
    assert flops.lm_forward_flops_per_token(cfg) == per_token
    assert per_token == 169_869_312 + 77_194_752
    # attention, causal: 1024 x 1025 / 2 pairs x 4 x 768 x 12 layers
    attn = 12 * 4 * (1024 * 1025 // 2) * 768
    step = 3 * 16 * (1024 * per_token + attn)
    assert flops.lm_train_flops_per_step(cfg, 16, 1024) == step
    assert 13.0e12 < step < 13.3e12
    f, b = flops.flash_train_flops_bytes(cfg, 16, 1024)
    assert f == 12 * 16 * 12 * (1024 * 1025 // 2) * 768
    assert b == 12 * 12 * 16 * 1024 * 768 * 2
    assert flops.decode_attention_bytes(cfg, 1000) == 12 * 2 * 1000 * 768 * 4


def test_resnet50_flops_by_hand():
    cfg = _cfg("resnet50")
    fwd = flops.resnet_v2_forward_flops(cfg)
    # 4.09 G multiply-adds is the figure quoted for ResNet-50 at 224 x 224
    # (stride on the 3x3, as here); 2 FLOPs each
    assert 2 * 4.0e9 < fwd < 2 * 4.2e9
    conv0 = 2 * 112 * 112 * 7 * 7 * 3 * 64
    fc = 2 * 2048 * 1000
    assert flops.resnet_v2_forward_flops(dict(cfg, units=[0, 0, 0, 0],
                                              filter_list=[64] * 5,
                                              num_classes=0)) == conv0
    assert flops.resnet_train_flops_per_step(cfg, 128) == 3 * 128 * fwd
    assert fwd > conv0 + fc


# -- traffic --------------------------------------------------------------------

def test_every_seed_gets_the_same_lengths_in_another_order():
    t = harness.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                       "serve-closed32.json"))
    pool = traffic.length_pool(t, 1024)
    assert len(pool) == t["pool"]
    assert all(32 <= p <= 768 and 16 <= a <= 256 and p + a <= 1024
               for p, a in pool)
    a = traffic.RequestStream(t, 50257, 1024, 1)
    b = traffic.RequestStream(t, 50257, 1024, 2**31 + 7)
    la = [tuple(map(len, (a.take()[0],))) for _ in range(t["pool"])]
    lb = [tuple(map(len, (b.take()[0],))) for _ in range(t["pool"])]
    assert sorted(la) == sorted(lb) and la != lb


def test_norm_gap_is_a_gap_of_norms():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "tiny": 2e-9}
    gaps = compare.leaf_gaps(prog, ref)
    assert max(gaps, key=gaps.get) == "a" and gaps["a"] == pytest.approx(0.1)
    assert gaps["tiny"] == pytest.approx(1e-9)
    assert compare.still_leaves(ref) == {"tiny"}


# -- every name in BENCHMARK.json resolves to its file --------------------------

def test_benchmark_json_resolves():
    from benchmark import run
    bench_file = os.path.join(ROOT, "BENCHMARK.json")
    bench = harness.load_json(bench_file)
    cells = [w["name"] for w in bench["workloads"]]
    for cell in cells:
        _b, entry, files, data = run.load_cell(bench_file, cell)
        assert data["traffic"]["driver"] in ("train", "serve_closed")
        files.find("drivers", data["traffic"]["driver"] + ".py")
        files.find("adapters", data["cfg"]["family"] + ".py")
        files.find("refs", data["cfg"]["family"] + ".py")
        e2e = run.metrics_of(bench, "end_to_end", cell)
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        per_layer = run.metrics_of(bench, "per_layer", cell)
        assert any("mfu" in m["name"] for m in per_layer)
        for m in e2e + per_layer:
            files.find("metrics", m["name"] + ".py")
    names = [m["name"] for m in bench["end_to_end"]]
    for m in bench["per_layer"]:
        assert m["moves"] in names and m["workloads"]
        assert set(m["workloads"]) <= set(cells)


# -- the drivers end to end at the tiny configuration ---------------------------

@pytest.fixture(scope="module")
def cpu_device():
    import mxnet_tpu  # noqa: F401
    import jax
    return jax.devices()[:1]


def _run(cell, device, trace_on=False, fault=None, seconds=0.4):
    from benchmark import run
    import io
    err = io.StringIO()
    result = run.execute(TINY, cell, 2**31 + 11, seconds, trace_on, device,
                         driver_class=FAULTS.get(fault), err=err,
                         peaks_for_tests=TEST_PEAKS)
    return result, err.getvalue()


# -- faults, planted in a subclass of the cell's driver: the timed path itself
# carries no branch for them

def _frozen_state(Driver):
    class Frozen(Driver):
        """A step that returns its state unchanged."""
        def call_step(self, inputs):
            import jax
            import jax.numpy as jnp
            kept = jax.tree_util.tree_map(jnp.copy, (self.params, self.mom,
                                                     self.aux))
            return kept + tuple(super().call_step(inputs)[3:])
    return Frozen


def _half_batch(Driver):
    class Half(Driver):
        """Half of the batch left out, the mean taken over the rest."""
        def next_batch(self):
            from benchmark.drivers import train
            return train.half_doubled(super().next_batch())
    return Half


def _altered_token(Driver):
    class Altered(Driver):
        """Tokens altered where they are produced."""
        def produced(self, out):
            import numpy as np
            tok = (np.array(out[0]) + 1) % self.cfg["vocab_size"]
            return (tok.astype(np.int32),) + tuple(out[1:])
    return Altered


def _inflated_count(Driver):
    class Inflated(Driver):
        """The engine's public token counts claim thrice the work."""
        def _counters(self):
            c = super()._counters()
            return dict(c, prefilled=3 * c["prefilled"],
                        decoded=3 * c["decoded"])
    return Inflated


FAULTS = {"frozen_state": _frozen_state, "half_batch": _half_batch,
          "altered_token": _altered_token, "inflated_count": _inflated_count}


# tiny.resnet-f32 is ResNet-50 on 64 x 64 images with the PROGRAM in float32:
# there the program and the plain reference agree to 0.4 % in the first
# gradient, which is what shows the reference follows the same mathematics
# (in bfloat16 a freshly initialised ResNet amplifies rounding to tenths)
@pytest.mark.parametrize("cell,metric", [("tiny.train", "train_step_ms"),
                                         ("tiny.serve", "serve_tokens_s"),
                                         ("tiny.resnet-f32", "train_step_ms")])
def test_sound_run_is_correct(cpu_device, cell, metric):
    result, err = _run(cell, cpu_device)
    assert result["correct"] is True, err
    assert list(result)[-1] == "compared"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"][metric]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert "limit" in err.splitlines()[-2] or "limit" in err.splitlines()[-1]


@pytest.mark.parametrize("cell,metric", [("tiny.train", "mfu_pct.train"),
                                         ("tiny.serve", "mfu_pct.serve")])
def test_traced_run_reports_per_layer_metrics(cpu_device, cell, metric):
    result, err = _run(cell, cpu_device, trace_on=True)
    assert result["correct"] is True, err
    assert result["metrics"][metric]["value"] > 0
    # no device plane on the CPU: the trace readers find nothing and are
    # left out, never reported as 0
    assert "flash_roofline" not in result["metrics"]
    assert "decode_attn_roofline" not in result["metrics"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "window_s" in result["device"]


@pytest.mark.parametrize("cell,fault", [
    ("tiny.train", "frozen_state"),     # a step that returns its state unchanged
    ("tiny.train", "half_batch"),       # half the batch left out, doubled rest
    ("tiny.serve", "altered_token"),    # tokens altered where they are produced
    ("tiny.serve", "inflated_count"),   # the counters serve_tokens_s reads
])
def test_planted_fault_is_not_correct(cpu_device, cell, fault):
    result, err = _run(cell, cpu_device, fault=fault)
    assert result["correct"] is False, err


def test_low_precision_control_fails_at_test_size(cpu_device):
    """The control (the reference, one precision below the configuration's,
    in the program's place) against the reference, at the tiny size."""
    import jax.numpy as jnp
    from benchmark import run
    _b, cell, files, data = run.load_cell(TINY, "tiny.train")
    ctx = run.Context(files=files, seed=5, devices=cpu_device,
                      spans=harness.Spans(), root=ROOT, cell=cell, **data)
    train = files.module("drivers", "train")
    drv = train.Driver(ctx)
    import numpy as np
    drv.batches = drv.adapter.train_batches(ctx.cfg, ctx.traffic, 5)
    shapes = drv.ref.param_shapes(ctx.cfg)
    drv.store_dtypes = {k: (np.float32 if k.endswith(("gamma", "beta"))
                            else jnp.bfloat16) for k in shapes}
    ref = drv.reference_readings()
    ctrl = drv.reference_readings(cast=jnp.float8_e4m3fn)
    checked = train.checks(ctrl, ref, ctx.limits)
    assert any(v > lim for _n, v, lim, _w in checked), checked


def test_reseed_reads_as_a_fresh_setup(cpu_device):
    """calibrate.py's path: the compiled step of one seed, re-seeded, gives
    what a driver set up on that seed gives."""
    from benchmark import run
    _b, cell, files, data = run.load_cell(TINY, "tiny.train")
    train = files.module("drivers", "train")

    def driver(seed):
        ctx = run.Context(files=files, seed=seed, devices=cpu_device,
                          spans=harness.Spans(), root=ROOT, cell=cell, **data)
        drv = train.Driver(ctx)
        drv.setup()
        return drv

    again = driver(7)
    again.reseed(8)
    fresh = driver(8).program_readings()
    for key in ("grad_norms", "change_norms"):
        assert again.program_readings()[key] == pytest.approx(fresh[key],
                                                              rel=1e-5)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_fails_at_test_size(cpu_device, seed):
    """The serving control: at every position of the same prompts and
    tokens, the token that a bfloat16 pass of the reference puts first lies
    below the float32 reference's best by more than the limit (at this width
    the logits are small: 3e-4 to 1.4e-3 against the tiny cell's 1e-4; the
    program itself reads 0)."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark import run
    _b, _cell, files, data = run.load_cell(TINY, "tiny.serve")
    ref = files.module("refs", data["cfg"]["family"])
    rs = np.random.default_rng(seed)
    vocab = data["cfg"]["vocab_size"]
    sample = [(rs.integers(0, vocab, 4).astype(np.int32),
               rs.integers(0, vocab, 12).astype(np.int32))
              for _ in range(64)]
    gap, n, _where = ref.served_token_gap(data["cfg"], seed, sample, 8,
                                          cast=jnp.bfloat16)
    assert n == 64 * 12 and gap > data["limits"]["served_logit_gap"]


@pytest.mark.parametrize("family,cfg_name", [
    ("transformer_lm", "tiny-lm"), ("resnet_v2", "tiny-resnet-f32")])
def test_reference_stays_float32_under_x64(cpu_device, family, cfg_name):
    """The program switches jax to 64-bit mode; a numpy scalar in the
    reference would then promote it to float64, which the TPU emulates (the
    first chip run of PR 25 planned 78 GB for it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    assert jax.config.jax_enable_x64
    ref = harness.load_module(os.path.join(ROOT, "benchmark", "refs",
                                           family + ".py"))
    cfg = harness.load_json(os.path.join(HERE, "tiny", "bench", "configs",
                                         cfg_name + ".json"))
    w = ref.make_weights(cfg, 2**31 + 3)
    assert {v.dtype for v in w.values()} == {jnp.dtype("float32")}
    if family == "transformer_lm":
        x = np.zeros((2, cfg["n_positions"]), np.int32)
        y = x
    else:
        x = np.zeros((2,) + tuple(cfg["image_shape"]), np.uint8)
        y = np.zeros((2,), np.int32)
    for cast in (None, jnp.float8_e4m3fn):
        (ce, probs), g = jax.value_and_grad(
            lambda p: ref.summed_loss(p, x, y, cfg, cast=cast),
            has_aux=True)(w)
        assert ce.dtype == probs.dtype == jnp.float32
        assert {v.dtype for v in g.values()} == {jnp.dtype("float32")}
