"""CPU tests of the Sarvam-MLA serving cell: ``python -m pytest
benchmark/tests/test_sarvam_mla.py -q`` (tier-1 collects them through
``tests/test_benchmark_sarvam_mla.py``).

The tiny cell beside this file (``tiny_sarvam_mla/``: the block at toy widths,
in float32 (at these widths a bfloat16 program's routing flips read as wide as
the control), 4 slots, 16 prompt rows a step, 4 of 8 experts held) goes through ``run.execute`` once sound and once with each fault planted
under the timed path; the float8 control is read as ``calibrate.py`` reads it;
the counts and the configuration are checked by hand against the catalog row.
``tests/test_mla_decode.py`` holds the program to the reference in float32.
"""
import io
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import harness  # noqa: E402
from benchmark.lib import sarvam_counts as counts  # noqa: E402

TINY = os.path.join(HERE, "tiny_sarvam_mla", "BENCHMARK.json")
CELL = "tiny.sarvam"
TEST_PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


@pytest.fixture(scope="module")
def cpu_device():
    import mxnet_tpu  # noqa: F401
    import jax
    return jax.devices()[:1]


def _published():
    return harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                          "sarvam-105b.json"))


# -- faults, planted in a subclass of the cell's driver ------------------------

def _altering_step(alter):
    """A Driver whose program's step gets its arguments through ``alter``
    (kv, tokens, positions, seq_lens, phys, off, page_table, prev_tok,
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


def _position_off_by_one(config, args):
    """Every chunk block's last live row says it stands one place on."""
    S = config.max_seqs
    positions = args[2] = np.array(args[2])
    chunk = positions[S:]
    last = np.flatnonzero((chunk >= 0)
                          & (np.append(chunk[1:], -1) < 0))
    positions[S + last] += 1
    return args


def _row_on_the_wrong_page(config, args):
    """A chunk's first row is written to the page its slot holds next."""
    S = config.max_seqs
    positions, table, row_slot = args[2], args[6], args[8]
    phys = args[4] = np.array(args[4])
    if positions[S] >= 0:
        pages = table[row_slot[S]]
        at = positions[S] // config.page_size
        phys[S] = pages[at + 1] if pages[at + 1] else pages[at - 1]
    return args


def _inflated_count(Driver):
    class Inflated(Driver):
        """The engine's public token counts claim thrice the work."""
        def _counters(self):
            c = super()._counters()
            return dict(c, prefilled=3 * c["prefilled"],
                        decoded=3 * c["decoded"])
    return Inflated


FAULTS = {"position_off_by_one": _altering_step(_position_off_by_one),
          "row_on_the_wrong_page": _altering_step(_row_on_the_wrong_page),
          "inflated_count": _inflated_count}


@pytest.fixture(scope="module")
def runs(cpu_device):
    from benchmark import run

    def execute(fault=None, trace_on=False, seed=2**31 + 11):
        err = io.StringIO()
        result = run.execute(TINY, CELL, seed, 0.5, trace_on, cpu_device,
                             driver_class=FAULTS.get(fault), err=err,
                             peaks_for_tests=TEST_PEAKS)
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
    for name in ("mla_attn_roofline", "moe_serve_roofline",
                 "moe_serve_device_pct", "device_idle_pct.serve"):
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
                                       (3, "bfloat16"), (1, "float8_e4m3fn"),
                                       (2, "float8_e4m3fn")])
def test_low_precision_control_fails_at_test_size(cpu_device, seed, cast):
    """The control as ``calibrate.py`` reads it (the precision below the
    cell's: bfloat16 for this float32 cell, float8_e4m3fn for the
    benchmark's bfloat16 one): at every position of the same prompts and
    tokens, the token that a pass of the reference with operands of that type
    puts first lies below the float32 reference's best by more than the tiny
    cell's limit."""
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


def test_span_readers_on_a_recorded_run():
    """The new per-layer readers' arithmetic on plain lists: the sums come
    from the ``serve/decode_step`` spans that began in the window, the
    seconds from the operations under ``mx.decode.moe``; a run without the
    attributes or the scope (the parent's) reads as nothing."""
    from benchmark.lib import decode_step_trace as dst
    step = "serve/decode_step"
    spans = [("python#1", step, 50.0, 10.0, {"attended": 900, "attn_pairs": 5}),
             ("python#1", step, 100.0, 10.0,
              {"attended": 100, "attn_pairs": 150, "expert_rows": 40,
               "experts_touched": "7"}),
             ("python#1", step, 200.0, 10.0,
              {"attended": 120, "attn_pairs": 170, "expert_rows": 44,
               "experts_touched": 8}),
             ("python#1", "serve/fetch", 210.0, 5.0, {"attended": 999})]
    path = "jit(step)/mx.decode.moe/experts/pallas_call"
    ops = [("/device:TPU:0", "gmm.1", path, 110.0, 2e9),
           ("/device:TPU:0", "fusion.2", "jit(step)/mx.decode.moe/route/dot",
            120.0, 1e9),
           ("/device:TPU:0", "fusion.3", "jit(step)/mx.decode.moelike/x",
            125.0, 1e9),
           ("/device:TPU:0", "mla_attn.4", "jit(step)/mx.decode.attn/x",
            130.0, 4e9)]
    run_ = (spans, ops, (90.0, 300.0))
    assert dst.step_sums(run_, ("attended", "attn_pairs")) \
        == {"attended": 220.0, "attn_pairs": 320.0}
    assert dst.step_sums(run_, ("expert_rows", "experts_touched")) \
        == {"expert_rows": 84.0, "experts_touched": 15.0}
    assert dst.step_counts(run_, ("attn_pairs", "attended")) \
        == [[150.0, 100.0], [170.0, 120.0]]
    assert dst.step_sums(run_, ("attended", "no_such")) is None
    assert dst.step_sums(None, ("attended",)) is None
    parts, total = dst.moe_seconds(run_)
    assert parts == {"experts": 2.0, "route": 1.0} and total == 8.0
    assert dst.moe_seconds((spans, ops[2:], (90.0, 300.0))) is None


# -- the counts and the configuration, by hand ---------------------------------------

def test_sarvam_105b_counts_by_hand():
    """The issue's arithmetic: attention 94.6 M parameters a layer, a routed
    expert 25.17 M, the cut 2,656 M; a cached token 5,760 B."""
    cfg = _published()
    part = counts.flops_per_token_by_part(cfg)
    attention = (part["q"] + part["kv_a"] + part["absorb"] + part["proj"]) / 2
    assert attention == 4096 * 12288 + 4096 * 576 + 512 * 16384 + 8192 * 4096
    assert round(attention / 1e6, 1) == 94.6
    assert part["routed"] / 2 == part["shared"] / 2 == 3 * 4096 * 2048
    assert part["dense_ffn"] / 2 == 3 * 4096 * 16384
    assert part["router"] / 2 == 4096 * 128
    # a token's stack: 5 attention blocks, the dense FFN, 4 x (router, shared
    # expert, ONE held pick expected: 8 picks x 16 / 128)
    assert counts.stack_flops_per_token(cfg) == 2 * (
        5 * attention + 3 * 4096 * 16384
        + 4 * (4096 * 128 + 2 * 3 * 4096 * 2048))
    assert counts.head_flops_per_token(cfg) == 2 * 4096 * 32768
    assert counts.row_width(cfg) == 576
    assert counts.latent_bytes(cfg, 1) * cfg["num_hidden_layers"] == 5760
    assert counts.absorbed_pair_flops(cfg) == 64 * 1088 * 2
    assert counts.expanded_pair_flops(cfg) == 5 * 64 * 320 * 2
    assert counts.upproject_flops(cfg) == 2 * 512 * 16384
    # decoding rows alone: the absorbed products over the pairs
    flops, bytes_ = counts.mla_attn_least(cfg, [(1000, 3000, 0, 0)])
    assert flops == 5 * 3000 * 139264 and bytes_ == 5 * 1000 * 1152
    # a chunk of r rows on a context of c: absorbed r (c - r / 2) x 139,264,
    # expanded the same pairs x 40,960 and c up-projections: they cross near
    # 170 rows, and the least is whichever is cheaper, step by step
    for r, form in ((128, "absorbed"), (448, "expanded")):
        c = 4096
        pairs = r * c - r * (r - 1) // 2
        flops, _ = counts.mla_attn_least(cfg, [(c, pairs, pairs, c)])
        assert flops == 5 * {"absorbed": pairs * 139264,
                             "expanded": pairs * 40960 + c * 16777216}[form]
        assert flops <= 5 * pairs * 139264
    both, _ = counts.mla_attn_least(
        cfg, [(1000, 3000, 0, 0), (4096, 1734880, 1734880, 4096)])
    assert both == 5 * 3000 * 139264 + flops
    flops, bytes_ = counts.held_experts_least(cfg, expert_rows=576,
                                              experts_touched=16)
    assert flops == 576 * 6 * 4096 * 2048
    assert bytes_ == 16 * 3 * 4096 * 2048 * 2
    # the parameters held: 2,656 M, 5.31 GB in bfloat16
    from benchmark.refs import sarvam_mla as ref
    held = sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values())
    assert round(held / 1e6) == 2656


def test_configuration_keeps_the_published_widths():
    """Every key of the catalog row's ``config`` is in the file under the same
    name with the same value, but the three ``reduced`` lists; no width among
    them."""
    cfg = _published()
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "sarvam-105b")
    published = {
        "attn_implementation": None, "default_theta": 10000,
        "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "sarvam_mla",
        "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
        "num_attention_heads": 64, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_shared_experts": 1, "q_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "deepseek_yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
        "vocab_size": 262144}
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == cfg["source"]
    for key, value in published.items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) \
        == (5, 16, 32768)
    assert cfg["router_width"] == 128 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["serving"] == {"dtype": "bfloat16"}
    for reading in ("routing", "expert_bias", "query", "use_qk_norm", "rope",
                    "no_bias", "shared_expert", "initializer_range",
                    "n_positions"):
        assert cfg["assumed"][reading]
    traffic = harness.load_json(os.path.join(
        ROOT, "benchmark", "traffic", "serve-closed64-4k.json"))
    assert traffic["slots"] == traffic["clients"] == 64
    assert traffic["slots"] * cfg["n_positions"] * 5760 == 3019898880


def test_reference_names_are_the_programs(cpu_device):
    from mxnet_tpu.models import sarvam_mla
    from benchmark.refs import sarvam_mla as ref
    cfg = _published()
    ours = sarvam_mla.param_shapes(sarvam_mla.model_of(cfg),
                                   cfg["num_hidden_layers"],
                                   cfg["vocab_size"])
    assert ours == ref.param_shapes(cfg)


def test_reference_stays_float32_under_x64(cpu_device):
    """The program switches jax to 64-bit mode; the reference's logits and
    its weights stay what they say."""
    import jax
    import jax.numpy as jnp
    from benchmark.refs import sarvam_mla as ref
    cfg = harness.load_json(os.path.join(
        HERE, "tiny_sarvam_mla", "bench", "configs", "tiny-sarvam.json"))
    with jax.enable_x64(True):
        p = ref.make_weights(cfg, 3)
        assert p["l0_q_weight"].dtype == jnp.float32
        low = ref.make_weights(dict(cfg, serving={"dtype": "bfloat16"}), 3)
        assert low["l0_q_weight"].dtype == jnp.bfloat16
        assert p["l0_ln1_gamma"].dtype == jnp.float32
        logits = ref.forward(p, np.arange(cfg["n_positions"],
                                          dtype=np.int32) % 96, cfg)
        assert logits.dtype == jnp.float32
        assert logits.shape == (cfg["n_positions"], 96)
