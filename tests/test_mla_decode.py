"""The latent-attention decode program (``serving/decode.py::
LatentDecodeProgram`` over ``models/sarvam_mla.py`` and the two kernels of the
latent pool) at a tiny size on the CPU: the kernels against their XLA
formulations, the absorbed program against the expanded plain reference
(``benchmark/refs/sarvam_mla.py``) through chunked prefill and decoding, the
YaRN frequencies by hand, the eight shares of an expert layer against the
whole, and the engine's contract over the many-token step."""
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_CFG = os.path.join(ROOT, "benchmark", "tests", "tiny_sarvam_mla", "bench",
                        "configs", "tiny-sarvam.json")
PUBLISHED_CFG = os.path.join(ROOT, "benchmark", "configs", "sarvam-105b.json")


def _cfg(**over):
    with open(TINY_CFG) as f:
        cfg = json.load(f)
    cfg["serving"] = {"dtype": "float32"}
    cfg.update(over)
    return cfg


def _ref():
    from benchmark.lib import harness
    return harness.load_module(os.path.join(ROOT, "benchmark", "refs",
                                            "sarvam_mla.py"))


def _program(cfg, seed=3, slots=4, chunk=16, page=8):
    from mxnet_tpu.models import sarvam_mla
    from mxnet_tpu.serving.decode import DecodeConfig, LatentDecodeProgram
    weights = {k: np.asarray(v) for k, v in
               _ref().make_weights(cfg, seed).items()}
    dc = DecodeConfig(cfg["vocab_size"], cfg["num_hidden_layers"],
                      cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["n_positions"], page_size=page, max_seqs=slots,
                      family="sarvam_mla", dtype=cfg["serving"]["dtype"],
                      prefill_tokens_per_step=chunk,
                      model=sarvam_mla.model_of(cfg))
    return LatentDecodeProgram(weights, dc, name="t"), weights


# -- the two kernels of the latent pool ---------------------------------------

def _mixed_rows(rs, S, chunk, page, n_pages, block):
    """Slot 0 decoding, slot 1 taking 20 prompt rows from position 5, slot 2
    idle: the step's per-row arrays and the slots' page table."""
    R = S + chunk
    table = np.zeros((S, n_pages), np.int32)
    table[0, :3] = [3, 7, 2]
    table[1, :5] = [5, 9, 11, 4, 6]
    positions = np.full(R, -1, np.int32)
    row_slot = np.zeros(R, np.int32)
    row_slot[:S] = np.arange(S)
    positions[0] = 12
    positions[S:S + 20] = 5 + np.arange(20)
    row_slot[S:S + -(-20 // block) * block] = 1
    live = positions >= 0
    phys = np.where(live, table[row_slot, np.maximum(positions, 0) // page], 0)
    off = np.where(live, positions % page, 0)
    return table, positions, row_slot, phys.astype(np.int32), \
        off.astype(np.int32)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 2e-2)])
def test_latent_kernels_equal_their_xla_formulations(dtype, tol):
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    rs = np.random.default_rng(0)
    L, P, page, width, latent, H, S = 2, 20, 8, 40, 32, 4, 3
    block = pk.mla_chunk_rows()
    chunk = 2 * block
    lanes = pk.latent_row_lanes(width)
    assert lanes == 128 and pk.latent_row_lanes(576) == 640
    table, positions, row_slot, phys, off = _mixed_rows(rs, S, chunk, page,
                                                        6, block)
    limit = np.where(positions >= 0, positions + 1, 0).astype(np.int32)
    pool = jnp.asarray(rs.normal(size=(L, P, page, lanes)), dtype)
    z = jnp.asarray(rs.normal(size=(S + chunk, width)), dtype)
    wrote = pk.latent_write(pool, 1, z, phys, off, use_pallas=True)
    plain = pk.latent_write(pool, 1, z, phys, off, use_pallas=False)
    a, b = np.asarray(wrote, np.float32), np.asarray(plain, np.float32)
    # all but the trash page, where dead rows land in any order
    assert np.array_equal(a[:, 1:], b[:, 1:])
    assert np.array_equal(a[0], np.asarray(pool, np.float32)[0])
    assert np.array_equal(a[1, 5, 5, :width],
                          np.asarray(z, np.float32)[S + 0])   # position 5
    assert not a[1, 5, 5, width:].any()
    q = jnp.asarray(rs.normal(size=(S + chunk, H, width)), dtype)
    args = (q, wrote, 1, table, row_slot, limit)
    kw = dict(n_decode=S, latent=latent, scale=0.3)
    u = np.asarray(pk.mla_attention(*args, use_pallas=True, **kw), np.float32)
    v = np.asarray(pk.mla_attention(*args, use_pallas=False, **kw), np.float32)
    assert np.isfinite(u).all()
    live = positions >= 0
    assert np.abs(u - v)[live].max() < tol


def test_one_rule_sizes_both_page_walks():
    """``decode_attn``'s G is what it was; ``mla_attn`` takes the same rule
    with one pool operand and its own token cap."""
    from mxnet_tpu.ops import pallas_kernels as pk
    assert pk._decode_pages_per_cell(12, 8, 128, 64, 4, 64) == 8
    assert pk._decode_pages_per_cell(32, 16, 128, 128, 4, 64) == 2
    assert pk._decode_pages_per_cell(
        1, 64, 640, 640, 2, 128, pools=1,
        cell_tokens=pk._MLA_CELL_TOKENS) == 8
    assert pk._decode_pages_per_cell(
        1, 64, 640, 640, 2, 3, pools=1, cell_tokens=pk._MLA_CELL_TOKENS) == 3


# -- YaRN ------------------------------------------------------------------------

def test_yarn_frequencies_by_hand():
    """sarvam-105b's own numbers: theta 10,000, 64 rope lanes, factor 40,
    original length 4,096, beta 32 / 1.  corr(32) = 64 ln(4096 / (64 pi)) /
    (2 ln 1e4) = 10.47, corr(1) = 22.51: pairs 0-10 keep their frequency,
    23-31 are slowed by 40, a ramp of thirteenths between."""
    from mxnet_tpu.models import sarvam_mla
    with open(PUBLISHED_CFG) as f:
        cfg = json.load(f)
    got = sarvam_mla.yarn_inv_freq(sarvam_mla.model_of(cfg))
    f = 10000.0 ** (-np.arange(32) / 32.0)
    assert got.shape == (32,) and got.dtype == np.float32
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=1e-6)
    # pair 16: f = 1e-2, gamma = 1 - 6/13
    assert got[16] == pytest.approx(0.01 * (6 / 13 / 40 + 7 / 13), rel=1e-6)
    assert got[11] == pytest.approx(f[11] * (1 / 13 / 40 + 12 / 13), rel=1e-6)
    m = 0.1 * np.log(40.0) + 1.0
    assert sarvam_mla.softmax_scale(sarvam_mla.model_of(cfg)) \
        == pytest.approx(192 ** -0.5 * m * m, rel=1e-9)
    assert m * m == pytest.approx(1.87386, rel=1e-5)
    np.testing.assert_allclose(got, _ref().inv_freq(cfg), rtol=1e-7)
    assert _ref().score_scale(cfg) == pytest.approx(0.135233, rel=1e-5)


# -- the absorbed program against the expanded reference -----------------------

def _teacher_forced(prog, ids, n_prompt, chunk_sizes):
    """Slot 1 takes ``ids[:n_prompt]`` in chunks of ``chunk_sizes`` and then
    the rest one row a step, teacher-forced; slot 2 decodes another sequence
    beside it.  Returns {position: slot 1's logits after that position}."""
    c = prog.config
    S, page = c.max_seqs, c.page_size
    R = prog.rows
    table = np.zeros((S, c.pages_per_seq), np.int32)
    n_pages = c.pages_per_seq
    table[1] = 1 + np.arange(n_pages)
    table[2] = 1 + n_pages + np.arange(n_pages)
    kv = prog.fresh_cache()
    logits = {}
    done = 0
    other = (np.arange(len(ids)) * 7 + 3) % c.vocab_size
    steps = list(chunk_sizes) + [1] * (len(ids) - n_prompt)
    for t, n in enumerate(steps):
        tokens = np.zeros(R, np.int32)
        positions = np.full(R, -1, np.int32)
        phys = np.zeros(R, np.int32)
        off = np.zeros(R, np.int32)
        row_slot = np.zeros(R, np.int32)
        row_slot[:S] = np.arange(S)
        seq_lens = np.zeros(S, np.int32)
        out_row = np.arange(S, dtype=np.int32)
        # slot 2: one row a step
        tokens[2], positions[2] = other[t], t
        phys[2], off[2], seq_lens[2] = table[2, t // page], t % page, t + 1
        pos = done + np.arange(n)
        if done >= n_prompt:            # slot 1 decodes: its own row
            rows = np.array([1])
        else:                           # slot 1's chunk rows
            rows = S + np.arange(n)
            row_slot[S:] = 1
            out_row[1] = rows[-1]
        tokens[rows], positions[rows] = ids[pos], pos
        phys[rows], off[rows] = table[1, pos // page], pos % page
        done += n
        seq_lens[1] = done
        _tok, lg, kv, _counts = prog.step(kv, tokens, positions, seq_lens,
                                          phys, off, table, None, row_slot,
                                          out_row)
        logits[done - 1] = np.asarray(lg[1])
    return logits


def test_chunked_prefill_then_decode_follows_the_reference():
    """Float32 on both sides: prompt rows taken 16, 7 and 12 to a step (a
    whole block, a padded one, two blocks with the last padded) and then
    decoding through the latent pool give the logits of the reference's one
    full forward pass in the expanded form: absorbed = expanded."""
    import jax
    cfg = _cfg()
    prog, weights = _program(cfg)
    rs = np.random.default_rng(5)
    ids = rs.integers(0, cfg["vocab_size"], 48).astype(np.int32)
    got = _teacher_forced(prog, ids, 35, (16, 7, 12))
    assert prog.trace_count == 1
    ref = _ref()
    padded = np.zeros(cfg["n_positions"], np.int32)
    padded[:48] = ids
    want = np.asarray(jax.jit(lambda p, i: ref.forward(p, i, cfg))(
        {k: np.asarray(v) for k, v in weights.items()}, padded))
    assert sorted(got) == [15, 22] + list(range(34, 48))
    scale = np.abs(want[:48]).max()
    for at, lg in got.items():
        assert np.abs(lg - want[at]).max() < 2e-4 * scale, at


# -- the share ---------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_whole_layer():
    """Eight chips hold 2 of 16 experts each; the routed parts the shares
    compute (``moe_ffn_held``, the program's layer), with the shared expert
    counted once, add up to the uncut reference layer."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import moe_ffn_held
    ref = _ref()
    cfg = _cfg(num_experts=16, router_width=16, num_experts_per_tok=4)
    p = {k: jnp.asarray(v) for k, v in ref.make_weights(cfg, 7).items()}
    pre = "l1_moe_"
    rs = np.random.default_rng(1)
    h = jnp.asarray(rs.normal(size=(24, cfg["hidden_size"])), jnp.float32)
    whole = np.asarray(ref.experts(p, pre, h, cfg))
    shared = tuple(p[pre + "shared_" + w] for w in ("w1", "w3", "w2"))
    kw = dict(num_experts=16, top_k=4, route_norm=True,
              route_scale=cfg["routed_scaling_factor"])
    total = np.zeros_like(whole)
    for chip in range(8):
        held = tuple(p[pre + "expert_" + w][2 * chip:2 * chip + 2]
                     for w in ("w1", "w3", "w2"))
        part, load = moe_ffn_held(h, p[pre + "router_weight"],
                                  jnp.zeros(16), None, held,
                                  first_expert=2 * chip, **kw)
        assert float(load.sum()) == 24 * 4
        total += np.asarray(part)
    held0 = tuple(p[pre + "expert_" + w][:2] for w in ("w1", "w3", "w2"))
    with_shared, _ = moe_ffn_held(h, p[pre + "router_weight"], jnp.zeros(16),
                                  shared, held0, first_expert=0, **kw)
    without, _ = moe_ffn_held(h, p[pre + "router_weight"], jnp.zeros(16),
                              None, held0, first_expert=0, **kw)
    total += np.asarray(with_shared) - np.asarray(without)
    assert np.abs(total - whole).max() < 1e-5 * np.abs(whole).max()


def test_dead_rows_pick_no_expert():
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import moe_ffn_held
    ref = _ref()
    cfg = _cfg()
    p = {k: jnp.asarray(v) for k, v in ref.make_weights(cfg, 7).items()}
    pre = "l1_moe_"
    h = jnp.asarray(np.random.default_rng(2).normal(
        size=(12, cfg["hidden_size"])), jnp.float32)
    live = jnp.arange(12) < 5
    held = tuple(p[pre + "expert_" + w] for w in ("w1", "w3", "w2"))
    kw = dict(num_experts=8, first_expert=0, top_k=2)
    out, load = moe_ffn_held(h, p[pre + "router_weight"], jnp.zeros(8), None,
                             held, live=live, **kw)
    alone, load5 = moe_ffn_held(h[:5], p[pre + "router_weight"], jnp.zeros(8),
                                None, held, **kw)
    assert float(load.sum()) == 5 * 2 == float(load5.sum())
    np.testing.assert_allclose(np.asarray(out[:5]), np.asarray(alone),
                               rtol=1e-5, atol=1e-6)
    assert not np.asarray(out[5:]).any()


# -- the engine over the many-token step ---------------------------------------------

def test_engine_keeps_its_contract_over_the_many_token_step(monkeypatch):
    """Requests of mixed lengths through ``submit()`` -> ``result()``: one
    trace over mixed chunk and decode rows, the step in flight, a decoding
    slot's token fed forward on the device, counts taken at fetch and equal
    to what was sent, and every served token the reference's own choice."""
    import jax
    from mxnet_tpu.serving.decode import DecodeEngine
    cfg = _cfg()
    prog, _weights = _program(cfg, seed=9)
    fed = []
    inner = prog.step

    def step(kv, tokens, *rest):
        fed.append(np.asarray(tokens).copy())
        return inner(kv, tokens, *rest)

    prog.step = step
    noted = []                  # what the serve/decode_step spans carry

    class Annotation:
        def __init__(self, name, **attrs):
            self.mine = name == "serve/decode_step"
            if self.mine:
                noted.append(dict(attrs))

        def set_metadata(self, **attrs):
            if self.mine:
                noted.append(dict(attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    eng = DecodeEngine(prog)
    rs = np.random.default_rng(0)
    lengths = ((5, 4), (23, 6), (40, 3), (1, 5), (17, 2), (9, 9), (33, 1))
    prompts = [rs.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n, _m in lengths]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, (_n, m) in zip(prompts, lengths)]
    served = [np.asarray(r.result(timeout=120)[0]) for r in reqs]
    st = eng.stats()
    eng.close()
    assert [len(s) for s in served] == [m for _n, m in lengths]
    assert prog.trace_count == 1 and st["decode"]["compiles"] == 1
    dec = st["decode"]
    assert dec["tokens_prefilled"] == sum(n for n, _m in lengths)
    assert dec["tokens_decoded"] == sum(m for _n, m in lengths)
    assert dec["steps_overlapped"] > 0
    assert dec["pool_bytes"] == prog.cache_bytes \
        == int(np.prod(prog.config.pool_shape())) * 4

    def total(key):
        return sum(a.get(key, 0) for a in noted)

    # a prompt row at position p attends p + 1 positions, and so does the
    # decoding row that follows the prompt: n + m - 1 rows a request
    assert total("n_prefill") == dec["tokens_prefilled"]
    assert total("chunk_pairs") == sum(n * (n + 1) // 2 for n, _m in lengths)
    assert total("attn_pairs") == sum((n + m - 1) * (n + m) // 2
                                      for n, m in lengths)
    # a prompt taken in k pieces is up-projected k times in the expanded form
    assert total("chunk_attended") >= sum(n for n, _m in lengths)
    assert total("expert_rows") > 0 and total("experts_touched") > 0
    S = prog.config.max_seqs
    # a decoding slot's row never carries its token through the host, and a
    # chunk row never stands for one
    assert any((t[:S] == -1).any() for t in fed)
    assert all((t[S:] >= 0).all() for t in fed)
    gap, n, where = _ref().served_token_gap(cfg, 9, list(zip(prompts, served)),
                                            1)
    assert n == sum(m for _n, m in lengths)
    assert gap < 1e-4, where


# -- family and dtype in the config and the artifact ---------------------------------

def test_config_and_artifact_carry_family_and_dtype(tmp_path):
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.serving import decode
    cfg = _cfg(serving={"dtype": "bfloat16"})
    prog, _w = _program(cfg)
    c = prog.config
    assert c.describe().startswith("sarvam_mla bfloat16 ")
    meta = c.to_meta()
    assert meta["family"] == "sarvam_mla" and meta["dtype"] == "bfloat16"
    assert meta["prefill_tokens_per_step"] == 16
    assert prog.cache_bytes == int(np.prod(c.pool_shape())) * 2
    assert str(prog.fresh_cache().dtype) == "bfloat16"
    assert c.pool_shape() == (3, 1 + 4 * 8, 8, 128)
    # names and shapes are the family's own
    assert set(decode.decode_param_shapes(c)) == set(prog._params)
    small = decode.DecodeConfig(96, 2, 32, 4, 16)
    assert small.family == "transformer_lm" and small.dtype == "float32"
    assert "l0_ff1_bias" in decode.decode_param_shapes(small)
    # each class builds its own family's step and no other
    with pytest.raises(MXNetError, match="describes sarvam_mla"):
        decode.DecodeProgram({}, c)
    with pytest.raises(MXNetError, match="describes transformer_lm"):
        decode.LatentDecodeProgram({}, small)
    with pytest.raises(MXNetError, match="float32"):
        decode.DecodeProgram({}, decode.DecodeConfig(96, 2, 32, 4, 16,
                                                     dtype="bfloat16"))
    # the artifact says what it is: either class loads it as that
    path = prog.export(str(tmp_path / "latent.mxd"))
    back = decode.DecodeProgram.load(path)
    assert type(back) is decode.LatentDecodeProgram
    assert back.config.same_geometry(c)
    assert str(back._params["l0_q_weight"].dtype) == "bfloat16"
    assert str(back._params["l0_ln1_gamma"].dtype) == "float32"
    # ... and one whose config was given another precision is refused
    from mxnet_tpu.resilience.container import read_container, write_container
    arrays, meta, blobs = read_container(path)
    meta["config"]["dtype"] = "float32"
    write_container(path, arrays=arrays, meta=meta, blobs=blobs)
    with pytest.raises(MXNetError, match="refusing to cast"):
        decode.DecodeProgram.load(path)
    meta["config"]["family"] = "no_such_family"
    write_container(path, arrays=arrays, meta=meta, blobs=blobs)
    with pytest.raises(MXNetError, match="no decode program"):
        decode.DecodeProgram.load(path)
