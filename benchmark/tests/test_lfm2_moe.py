"""CPU tests of the LFM2-MoE serving cell: ``python -m pytest
benchmark/tests/test_lfm2_moe.py -q`` (tier-1 collects them through
``tests/test_benchmark_lfm2_moe.py``).

The tiny cell beside this file (``tiny_lfm2_moe/``: the block at toy widths
in float32, 2 dense and 4 more layers of the published pattern, 8 query heads
on 2 key/value heads, all 8 experts held with 2 picks, 4 slots, 16 prompt
rows a step) goes through ``run.execute`` once sound and once with each fault
planted under the timed path; the low-precision control is read as
``calibrate.py`` reads it; the new readers are checked on a hand-made trace;
the counts and the configuration are checked by hand against the catalog
row.  ``tests/test_lfm2_decode.py`` holds the program to the reference.
"""
import io
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import harness, program_trace  # noqa: E402
from benchmark.lib import lfm2_counts as counts  # noqa: E402

TINY = os.path.join(HERE, "tiny_lfm2_moe", "BENCHMARK.json")
CELL = "tiny.lfm2"
TEST_PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
PUBLISHED_LAYERS = [
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv"]


@pytest.fixture(scope="module")
def cpu_device():
    import mxnet_tpu  # noqa: F401
    import jax
    return jax.devices()[:1]


def _published():
    return harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                          "lfm2-8b-a1b.json"))


def _metric(name):
    return harness.load_module(os.path.join(ROOT, "benchmark", "metrics",
                                            name + ".py"))


# -- faults, planted in a subclass of the cell's driver ------------------------

def _altering_step(alter):
    """A Driver whose program's step gets its arguments through ``alter``
    (state, tokens, positions, seq_lens, phys, off, page_table, prev_tok,
    row_slot, out_row) -> the same, changed."""
    def wrap(Driver):
        class Altered(Driver):
            def _wrap_step(self):
                inner = self.prog.step
                config = self.prog.config
                self.prog.step = lambda *args: inner(*alter(config,
                                                            list(args)))
                super()._wrap_step()
        return Altered
    return wrap


def _chunk_split_from_its_slot(config, args):
    """A chunk's rows from its third on are counted as another slot's, so
    their convolution reads that slot's state where it should read the rows
    before them (and their attention that slot's pages)."""
    S = config.max_seqs
    positions = args[2]
    row_slot = args[8] = np.array(args[8])
    chunk = positions[S:]
    live = np.flatnonzero(chunk >= 0)
    if live.size > 2:
        row_slot[S + live[2:]] = (row_slot[S + live[2:]] + 1) % S
    return args


def _state_left_behind(config, args):
    """Every step starts from a fresh state: what the slots carried is
    lost, the K/V pool kept."""
    import jax.numpy as jnp
    state = args[0]
    args[0] = {"kv": state["kv"], "conv": jnp.zeros_like(state["conv"])}
    return args


def _inflated_count(Driver):
    class Inflated(Driver):
        """The engine's public token counts claim thrice the work."""
        def _counters(self):
            c = super()._counters()
            return dict(c, prefilled=3 * c["prefilled"],
                        decoded=3 * c["decoded"])
    return Inflated


FAULTS = {"chunk_split_from_its_slot":
          _altering_step(_chunk_split_from_its_slot),
          "state_left_behind": _altering_step(_state_left_behind),
          "inflated_count": _inflated_count}


@pytest.fixture(scope="module")
def runs(cpu_device, tmp_path_factory):
    from benchmark import run

    def execute(fault=None, trace_on=False, seed=2**31 + 13):
        err = io.StringIO()
        # a trace directory of these runs' own: the checkout's is emptied by
        # every traced run, and other workers make such runs meanwhile
        traces = str(tmp_path_factory.mktemp("trace"))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(harness, "trace_dir", lambda root: traces)
            mp.setattr(program_trace, "TRACE_DIR", traces)
            result = run.execute(TINY, CELL, seed, 0.5, trace_on,
                                 cpu_device, driver_class=FAULTS.get(fault),
                                 err=err, peaks_for_tests=TEST_PEAKS)
        return result, err.getvalue()
    return execute


def test_sound_run_of_the_tiny_cell_is_correct(runs):
    result, err = runs(trace_on=True)
    assert result["correct"] is True, err
    assert result["attempted"] > 0 and result["failed"] == 0
    m = result["metrics"]
    assert m["mfu_pct.serve"]["value"] > 0
    assert m["engine_step_ms"]["value"] > 0
    assert 1 < m["prefill_rows_per_step"]["value"] <= 16
    assert 0 < m["kv_pool_live_pct"]["value"] <= 100
    # no device plane on the CPU: the trace readers find nothing and the
    # metrics are left out, as on a parent commit without the step
    for name in ("conv_device_pct", "gqa_attn_roofline",
                 "moe_serve_roofline", "device_idle_pct.serve"):
        assert name not in m
    assert set(result["compared"]) == {
        "served_logit_gap", "requests_checked_short", "tokens_miscounted",
        "compiled_in_window"}
    assert result["compared"]["tokens_miscounted"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_in_the_tiny_cell_is_not_correct(runs, fault):
    result, err = runs(fault=fault)
    assert result["correct"] is False, err
    assert result["failed"] == 0        # it served, and served wrong


@pytest.mark.parametrize("seed,cast", [(1, "bfloat16"), (2, "bfloat16"),
                                       (1, "float8_e4m3fn"),
                                       (2, "float8_e4m3fn")])
def test_low_precision_control_fails_at_test_size(cpu_device, seed, cast):
    """The control as ``calibrate.py`` reads it (the precision below the
    cell's: bfloat16 for this float32 cell, float8_e4m3fn for the
    benchmark's bfloat16 one): the token that a pass of the reference with
    operands of that type puts first lies below the float32 reference's best
    by more than three times the tiny cell's limit."""
    import jax.numpy as jnp
    from benchmark import run
    _b, _cell, files, data = run.load_cell(TINY, CELL)
    ref = files.module("refs", data["cfg"]["family"])
    rs = np.random.default_rng(seed)
    vocab = data["cfg"]["vocab_size"]
    sample = [(rs.integers(0, vocab, 20).astype(np.int32),
               rs.integers(0, vocab, 12).astype(np.int32))
              for _ in range(8)]
    gap, n, _where = ref.served_token_gap(data["cfg"], seed, sample, 1,
                                          cast=getattr(jnp, cast))
    assert n == 8 * 12 and gap > 3 * data["limits"]["served_logit_gap"]


# -- the new readers on a hand-made trace -------------------------------------------

STEP = "serve/decode_step"
TPU = "/device:TPU:0"


def _spans():
    """Three steps: one before the window (left out), two inside."""
    return [("python#1", STEP, 10.0, 5.0, {"attended": 9, "attn_pairs": 9}),
            ("python#1", STEP, 100.0, 5.0,
             {"attended": 150_000, "attn_pairs": 400_000, "state_rows": 130}),
            ("python#1", STEP, 200.0, 5.0,
             {"attended": 160_000, "attn_pairs": 500_000, "state_rows": 129}),
            ("python#1", "serve/fetch", 210.0, 1.0, {"attended": 999})]


def _ops():
    conv = "jit(step)/mx.decode.conv/shift/scatter"
    return [(TPU, "fusion.1", conv, 110.0, 3e6),
            (TPU, "fusion.2", "jit(step)/mx.decode.conv/in_proj/dot",
             120.0, 1e6),
            (TPU, "fusion.3", "jit(step)/mx.decode.moe/experts/gmm", 130.0,
             12e6),
            (TPU, "fusion.4", "jit(step)/mx.decode.convex/x", 150.0, 4e6)]


def test_conv_device_pct_on_a_recorded_run(monkeypatch):
    read = _metric("conv_device_pct").read
    monkeypatch.setattr(program_trace, "of_run",
                        lambda facts: (_spans(), _ops(), (50.0, 300.0)))
    # 3 + 1 ms under mx.decode.conv of 20 ms on the device
    assert read({"events": [1]}) == pytest.approx(20.0)
    monkeypatch.setattr(program_trace, "of_run",
                        lambda facts: (_spans(), _ops()[2:], (50.0, 300.0)))
    assert read({"events": [1]}) is None
    monkeypatch.setattr(program_trace, "of_run", lambda facts: None)
    assert read({}) is None


def test_gqa_attn_roofline_on_a_recorded_run(monkeypatch):
    """310,000 positions attended (K and V of 3 layers x 8 heads x 64 lanes
    in bfloat16: 6,144 B each, 1.905 GB) against 900,000 pairs (3 layers x
    4 x 32 heads x 64 lanes: 22.1 GFLOP): bytes bound it, 2.326 ms at
    819 GB/s, over 2 + 2 ms of the two kernels."""
    read = _metric("gqa_attn_roofline").read
    cfg = _published()
    monkeypatch.setattr(program_trace, "of_run",
                        lambda facts: (_spans(), [], (50.0, 300.0)))
    events = [(TPU, "XLA Ops", "decode_attn.3", 110.0, 2e6),
              (TPU, "XLA Ops", "chunk_attn.4", 120.0, 2e6),
              (TPU, "XLA Ops", "kv_write.5", 130.0, 5e6)]
    facts = {"events": events, "cfg": cfg,
             "peaks": {"flops": 197e12, "hbm_bytes_per_s": 819e9}}
    bytes_ = 310_000 * 6144
    flops = 900_000 * 3 * 4 * 32 * 64
    assert bytes_ / 819e9 > flops / 197e12
    assert read(facts) == pytest.approx(100.0 * bytes_ / 819e9 / 4e-3)
    assert 0 < read(facts) <= 100
    facts["events"] = events[2:]            # neither kernel ran
    assert read(facts) is None
    plain = [s[:4] + ({"n_prefill": 3},) for s in _spans()]
    monkeypatch.setattr(program_trace, "of_run",
                        lambda facts: (plain, [], (50.0, 300.0)))
    assert read(dict(facts, events=events)) is None


# -- the counts and the configuration, by hand ---------------------------------------

def test_lfm2_counts_by_hand():
    """The configuration's arithmetic: an expert layer 352.39 M parameters, a conv
    mixer 16.78 M, an attention mixer 10.49 M, a dense FFN 44.04 M, the
    embedding 134.2 M, the whole model 8.34 B and the cut 3.93 B; a cached
    position 6,144 B; a slot's convolution state 110,592 B."""
    cfg = _published()
    part = counts.flops_per_token_by_part(cfg)
    d = 2048
    assert part["conv_in"] / 2 == 3 * d * d and part["conv_out"] / 2 == d * d
    assert part["shift"] == 2 * d * 3 + 2 * d
    assert part["qkv"] / 2 == d * (32 + 16) * 64 and part["o"] / 2 == d * d
    assert part["dense_ffn"] / 2 == 3 * d * 7168
    assert part["router"] / 2 == d * 32 and part["routed"] / 2 == 3 * d * 1792
    expert_layer = 32 * 3 * d * 1792 + d * 32
    conv_mixer = 4 * d * d + 3 * d
    attn_mixer = 2 * d * d + 2 * 512 * d + 2 * 64
    dense = 3 * d * 7168
    assert round(expert_layer / 1e4) == 35239
    assert round(conv_mixer / 1e4) == 1678
    assert round(attn_mixer / 1e4) == 1049
    assert round(dense / 1e4) == 4404
    assert round(65536 * d / 1e5) == 1342
    norms = 2 * d

    def model(kinds):
        n = len(kinds)
        return (sum(conv_mixer if k == "conv" else attn_mixer for k in kinds)
                + 2 * dense + (n - 2) * expert_layer + n * norms + d
                + 65536 * d)

    assert round(model(PUBLISHED_LAYERS) / 1e7) == 834
    assert round(model(PUBLISHED_LAYERS[:12]) / 1e7) == 393
    from benchmark.refs import lfm2_moe as ref
    held = sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values())
    assert held == model(PUBLISHED_LAYERS[:12])
    # a token's stack: 9 conv and 3 attention mixers, 2 dense FFNs, 10 x
    # (router + 4 experts)
    assert counts.stack_flops_per_token(cfg) == 2 * (
        9 * conv_mixer - 9 * 3 * d + 9 * (3 * d + d) + 3 * (attn_mixer - 128)
        + 2 * dense + 10 * (d * 32 + 4 * 3 * d * 1792))
    assert counts.head_flops_per_token(cfg) == 2 * d * 65536
    assert counts.pair_flops(cfg) == 3 * 4 * 32 * 64
    assert counts.kv_bytes_per_position(cfg) == 6144 == 3 * 2 * 8 * 64 * 2
    assert counts.conv_state_bytes(cfg, 1) == 9 * 3 * d * 2 == 110592
    flops, bytes_ = counts.gqa_attn_least(cfg, [(1000, 3000), (500, 700)])
    assert flops == 3700 * 3 * 4 * 32 * 64 and bytes_ == 1500 * 6144


def test_configuration_keeps_the_published_widths():
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but the two ``reduced``; no width among
    them; the cut and the deployment stated beside them."""
    cfg = _published()
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": PUBLISHED_LAYERS,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert entry["source"] == cfg["source"]
    for key, value in published.items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 12
    assert cfg["layer_types"] == PUBLISHED_LAYERS[:12]
    assert cfg["serving"] == {"dtype": "bfloat16"}
    assert "two-stage pipeline" in cfg["deployment"]
    for reading in ("tied_head", "conv_split", "qk_norm", "rope", "routing",
                    "expert_bias", "n_positions", "initializer_range",
                    "precision"):
        assert cfg["assumed"][reading]
    traffic = harness.load_json(os.path.join(
        ROOT, "benchmark", "traffic", "serve-closed128-4k.json"))
    assert traffic["slots"] == traffic["clients"] == 128
    assert traffic["prefill_tokens_per_step"] == 384
    # the pool: 128 slots x 4,096 positions x 6,144 B
    assert traffic["slots"] * cfg["n_positions"] * 6144 == 3221225472


def test_reference_names_are_the_programs(cpu_device):
    from mxnet_tpu.models import lfm2_moe
    from benchmark.refs import lfm2_moe as ref
    cfg = _published()
    ours = lfm2_moe.param_shapes(lfm2_moe.model_of(cfg),
                                 cfg["num_hidden_layers"], cfg["vocab_size"])
    assert ours == ref.param_shapes(cfg)


def test_reference_hands_out_host_arrays_in_the_serving_type(cpu_device):
    """The program switches jax to 64-bit mode; the reference's weights are
    host arrays of the type they say, its hidden states float32."""
    import jax
    import jax.numpy as jnp
    from benchmark.refs import lfm2_moe as ref
    cfg = harness.load_json(os.path.join(
        HERE, "tiny_lfm2_moe", "bench", "configs", "tiny-lfm2.json"))
    with jax.enable_x64(True):
        p = ref.make_weights(cfg, 3)
        assert isinstance(p["l0_in_proj_weight"], np.ndarray)
        assert p["l0_in_proj_weight"].dtype == np.float32
        low = ref.make_weights(dict(cfg, serving={"dtype": "bfloat16"}), 3)
        assert low["l0_in_proj_weight"].dtype == jnp.bfloat16
        assert low["l0_ln1_gamma"].dtype == np.float32
        ids = (np.arange(cfg["n_positions"], dtype=np.int32) % 96)[None]
        u = ref.final_hidden(p, ids, cfg)
        assert u.dtype == jnp.float32
        assert u.shape == (1, cfg["n_positions"], cfg["hidden_size"])
