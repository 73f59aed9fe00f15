"""GPT-2's many-token decode step: the slots' own rows and a chunk of prompt
rows in blocks of ``chunk_attn_rows()`` under one budget, compiled once.

CPU: the XLA formulation and the Pallas interpreter.  The step compiled for
a described v5e lives in ``test_decode_pool.py`` beside that file's other
compile (one file holds the topology fixture)."""
import hashlib
import re
import types

import jax
import numpy as np
import pytest

import mxnet_tpu  # noqa: F401  (x64 + matmul precision config)
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.serving import decode as d
from mxnet_tpu.serving.decode import (DecodeConfig, DecodeEngine,
                                      DecodeProgram, init_decode_params)

VOCAB = 61


def _config(budget, layers=2, max_seqs=3):
    return DecodeConfig(VOCAB, layers, 32, 4, 64, page_size=4,
                        max_seqs=max_seqs, prefill_tokens_per_step=budget)


def _serve(prog):
    """Greedy answers to prompts longer than the budget, shorter than a
    block, ending mid-block, several of them in their prompt at once (six
    requests on three slots), and ``forward``'s answer."""
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, VOCAB, n) for n in (5, 37, 18, 1, 23, 9)]
    with DecodeEngine(prog, default_deadline=120.0) as eng:
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        outs = [np.asarray(r.result(timeout=120)[0]) for r in reqs]
        st = eng.stats()
    c = prog.config
    fwd = prog.forward(np.arange(c.max_seqs * c.forward_len).reshape(
        c.max_seqs, c.forward_len) % VOCAB)[0]
    return outs, fwd, st


@pytest.fixture(scope="module")
def one_token():
    prog = DecodeProgram(init_decode_params(_config(0), seed=5), _config(0),
                         name="one")
    assert prog.rows == prog.config.max_seqs
    return _serve(prog)


@pytest.mark.parametrize("knob,budget", [("0", 16), ("1", 16), ("1", 32)],
                         ids=["xla-16", "pallas-16", "pallas-32"])
def test_chunk_step_tokens_equal_one_token_step(one_token, monkeypatch, knob,
                                                budget):
    monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE", knob)
    cfg = _config(budget)
    prog = DecodeProgram(init_decode_params(cfg, seed=5), cfg, name="chunk")
    outs, fwd, st = _serve(prog)
    ref_outs, ref_fwd, ref_st = one_token
    assert all(np.array_equal(a, b) for a, b in zip(outs, ref_outs))
    assert np.array_equal(fwd, ref_fwd)
    # every prompt row counted once, as prefilled; the one-token step counts
    # a prompt's last token as the decode step it is
    prompts = 5 + 37 + 18 + 1 + 23 + 9
    assert st["decode"]["tokens_prefilled"] == prompts
    assert ref_st["decode"]["tokens_prefilled"] == prompts - 6
    assert st["decode"]["tokens_decoded"] == ref_st["decode"][
        "tokens_decoded"] == 36
    assert st["counters"]["steps"] < ref_st["counters"]["steps"]
    assert prog.trace_count == 1 and st["decode"]["compiles"] == 1


# -- the kernel --------------------------------------------------------------

# (head_dim, page): one token a pool row; two (GPT-2's case in small); four
GEOMETRIES = [(8, 4), (64, 4), (32, 8)]


@pytest.mark.parametrize("D,page", GEOMETRIES)
@pytest.mark.parametrize("G", [1, 2, None], ids=["G1", "G2", "rule"])
def test_chunk_kernel_equals_xla_formulation(monkeypatch, D, page, G):
    """Blocks of one slot at a time: a dead block first and between live
    ones, a block whose rows end mid-block, one that starts mid-page, one
    whose context spans several groups; the interpreter's kernel against the
    XLA formulation, for every layer of the pool."""
    if G is not None:
        monkeypatch.setattr(pk, "_decode_pages_per_cell",
                            lambda *a, **k: G)
    rs = np.random.RandomState(D + page)
    L, P, H = 2, 33, 2
    pack = pk.kv_pack(page, D)
    kv = rs.randn(L, 2, P, H, page // pack, pack * D).astype(np.float32)
    # four slots of eight pages each, shuffled over the pool
    table = 1 + rs.permutation(P - 1).reshape(4, 8).astype(np.int32)
    TQ = pk.chunk_attn_rows()
    C = 5 * TQ
    q = rs.randn(C, H, D).astype(np.float32)
    row_slot = np.zeros(C, np.int32)
    limit = np.zeros(C, np.int32)

    def block(b, slot, first, n):
        rows = slice(b * TQ, b * TQ + n)
        row_slot[b * TQ:(b + 1) * TQ] = slot
        limit[rows] = first + np.arange(n) + 1

    # block 0 dead
    block(1, 0, 3, 11)                  # starts mid-page, ends mid-block
    block(2, 2, 2 * page, TQ)           # a context over two or more groups
    # block 3 dead
    block(4, 3, 0, 5)
    for layer in range(L):
        got = np.asarray(pk.chunk_attention(q, kv, layer, table, row_slot,
                                            limit, use_pallas=True))
        want = np.asarray(pk.chunk_attention(q, kv, layer, table, row_slot,
                                             limit, use_pallas=False))
        live = limit > 0
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got[live], want[live], rtol=1e-5,
                                   atol=1e-5)
        # a dead block's rows are zero, and nothing of them is read
        assert not got[:TQ].any() and not got[3 * TQ:4 * TQ].any()


def test_chunk_kernel_refuses_a_partial_block():
    q = np.zeros((pk.chunk_attn_rows() + 1, 2, 8), np.float32)
    kv = np.zeros((1, 2, 3, 2, 4, 8), np.float32)
    with pytest.raises(ValueError):
        pk.chunk_attention(q, kv, 0, np.zeros((1, 2), np.int32),
                           np.zeros(len(q), np.int32),
                           np.zeros(len(q), np.int32), use_pallas=True)


# -- the budget ----------------------------------------------------------------

@pytest.fixture
def on_a_tpu(monkeypatch):
    """What :meth:`DecodeProgram.derived_budget` sees on one TPU with the
    Pallas kernels (the decision alone; nothing is run)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pk, "decode_backend_is_pallas", lambda *a: True)


@pytest.mark.parametrize("S,budget", [(8, 112), (32, 96), (64, 64),
                                      (120, 128), (128, 128)])
def test_derived_budget_follows_the_rule(on_a_tpu, S, budget):
    """The most whole blocks that fill the fewest 128-row tiles holding one
    block beside the slots' rows."""
    cfg = DecodeConfig(50257, 12, 768, 12, 1024, page_size=16, max_seqs=S)
    assert cfg.prefill_tokens_per_step is None
    got = DecodeProgram.derived_budget(cfg)
    block = pk.chunk_attn_rows()
    assert got == budget and got % block == 0 and got >= block
    rows = S + got
    assert rows <= -(-(S + block) // 128) * 128 < rows + block


def test_derived_budget_only_on_one_tpu_with_the_kernels(on_a_tpu,
                                                        monkeypatch):
    cfg = DecodeConfig(50257, 12, 768, 12, 1024, page_size=16, max_seqs=32)
    assert DecodeProgram.derived_budget(cfg, mesh={"tp": 2}) == 0
    monkeypatch.setattr(pk, "decode_backend_is_pallas", lambda *a: False)
    assert DecodeProgram.derived_budget(cfg) == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setattr(pk, "decode_backend_is_pallas", lambda *a: True)
    assert DecodeProgram.derived_budget(cfg) == 0


def test_a_budget_the_config_names_is_used_as_given():
    """An explicit number, 0 included, is the program's budget; where the
    config names none the CPU derives 0, the one-token step."""
    params = init_decode_params(_config(0), seed=1)
    for given, rows in ((0, 3), (48, 51), (None, 3)):
        prog = DecodeProgram(params, _config(given), name="given")
        assert prog.config.prefill_tokens_per_step == rows - 3
        assert prog.rows == rows
        names = [f[0] for f in prog._operands.fields]
        assert ("row_slot" in names) == bool(rows - 3)
    with pytest.raises(mxnet_tpu.base.MXNetError):
        DecodeProgram(params, _config(24), name="half a block")


# -- one program, traced once -----------------------------------------------

def test_trace_count_is_one_across_an_engine_life():
    """The engine's steps, ``forward`` and a caller with one row a slot go
    through the one jitted call of the many-token step."""
    cfg = _config(16)
    prog = DecodeProgram(init_decode_params(cfg, seed=2), cfg, name="life")
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        eng.generate(np.arange(40) % VOCAB, max_new_tokens=3)
        eng.generate(np.arange(3) % VOCAB, max_new_tokens=2)
        st = eng.stats()
    prog.forward(np.zeros((cfg.max_seqs, cfg.forward_len), np.int32))
    S = cfg.max_seqs
    table = np.zeros((S, cfg.pages_per_seq), np.int32)
    table[:, 0] = 1 + np.arange(S)
    pos = np.zeros(S, np.int32)
    out = prog.step(prog.fresh_cache(), np.full(S, 7, np.int32), pos,
                    pos + 1, table[:, 0].copy(), pos, table)
    assert np.asarray(out[0]).shape == (S,)
    assert st["decode"]["compiles"] == 1 and st["decode"][
        "host_operands_per_step"] == 1.0
    assert prog.trace_count == 1
    assert prog._jit_step._cache_size() == 1


def test_kernels_are_traced_and_lowered_once_a_step(monkeypatch):
    """Every Pallas call the step makes a layer is a jitted call with the
    layer an operand: at four layers each kernel's body is traced once and
    the lowered module holds one function for it, called four times (a
    bare ``pallas_call`` a layer would trace and lower it four times: the
    set-up PR 34 and PR 38 paid)."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE", "1")
    traced = {}
    for name in ("_kv_write_kernel", "_decode_attn_kernel",
                 "_chunk_attn_kernel"):
        inner = getattr(pk, name)

        def counting(*a, _inner=inner, _name=name, **k):
            traced[_name] = traced.get(_name, 0) + 1
            return _inner(*a, **k)
        monkeypatch.setattr(pk, name, counting)
    for call in (pk._kv_write_call, pk._decode_attn_call,
                 pk._chunk_attn_call):
        call.clear_cache()
    cfg = _config(16, layers=4, max_seqs=2)
    prog = DecodeProgram(init_decode_params(cfg, seed=3), cfg, name="lower")
    lowered = jax.jit(prog._packed_step_fn(count=False)).lower(
        prog._param_leaves, prog.fresh_cache(), *prog._warm_args())
    assert traced == {"_kv_write_kernel": 1, "_decode_attn_kernel": 1,
                      "_chunk_attn_kernel": 1}
    text = lowered.as_text()
    for call in ("_kv_write_call", "_decode_attn_call", "_chunk_attn_call"):
        assert len(re.findall(r"func\.func private @%s\b" % call, text)) \
            == 1, call
        assert len(re.findall(r"call @%s\b" % call, text)) \
            == cfg.num_layers, call


# -- the engine's rows for a latent program ------------------------------------

def test_build_rows_of_a_latent_program_is_unchanged():
    """``_build_rows`` for a latent program at 384 rows beside 64 slots: the
    arrays, takers and counts of three steps in a row hash to what they were
    before GPT-2 took the same step (the digest was taken with PR 37's
    ``serving/decode.py``).  Slots that decode, hold prompt longer and
    shorter than the budget, end their prompt mid-block, or are empty."""
    c = DecodeConfig(1000, 2, 64, 4, 8192, page_size=64, max_seqs=64,
                     family=d.SARVAM_MLA, dtype="bfloat16",
                     prefill_tokens_per_step=384, model={})
    rs = np.random.RandomState(39)
    slots = [None] * c.max_seqs
    for i in rs.permutation(c.max_seqs)[:52]:
        n_prompt = int(rs.choice([5, 17, 100, 383, 384, 385, 900, 2000]))
        req = d.DecodeRequest(rs.randint(0, 1000, n_prompt),
                              int(rs.randint(1, 300)),
                              seq=int(rs.randint(0, 10 ** 6)))
        slot = d._Slot(req, list(range(1 + 128 * int(i),
                                       1 + 128 * int(i) + 128)))
        slot.pos = int(rs.choice([0, 0, 3, n_prompt - 1, n_prompt,
                                  n_prompt + 7]))
        slot.pos = min(max(slot.pos, 0), n_prompt + 7)
        slots[int(i)] = slot
    fake = types.SimpleNamespace(
        _slots=slots, _program=types.SimpleNamespace(chunk_block=16))
    h = hashlib.sha256()
    for _ in range(3):
        active = [i for i, s in enumerate(slots) if s is not None]
        arrays, takers, counts = DecodeEngine._build_rows(fake, active, c)
        for a in arrays:
            h.update(np.ascontiguousarray(a, np.int32).tobytes())
        h.update(repr([(i, r.seq, bool(t), bool(last), int(n))
                       for i, r, t, last, n in takers]).encode())
        h.update(repr(sorted((k, int(v)) for k, v in counts.items()))
                 .encode())
    assert h.hexdigest() == ("946504bf1dc3b27481f664dbcbc6945d"
                             "485f2c8926f4541016d9a1b5ac21bbc5")
