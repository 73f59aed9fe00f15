"""The decode step and its page pool: the two kernels that touch the pool
(``kv_write``, the six-axis entry of ``decode_attn``) take it whole, and
nothing else in the step touches it.

CPU, Pallas interpreter — except the last test, which compiles the 64-slot
step for a described v5e (no chip; skipped where none can be described).
The topology is described inside a fixture, never at import.
"""
import os
import re

import jax
import numpy as np
import pytest

import mxnet_tpu  # noqa: F401  (x64 + matmul precision config)
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.serving.decode import (DecodeConfig, DecodeProgram,
                                      decode_param_shapes,
                                      init_decode_params)

LAYERS = 3


# -- (a) decode_attn, six-axis entry --------------------------------------

def _attention_reference(q, kp, vp, pt, lens):
    """numpy reference of test_decode.py's paged-attention test."""
    S, nH, D = q.shape
    ref = np.zeros((S, nH, D), np.float32)
    for s in range(S):
        tl = int(lens[s])
        if tl == 0:
            continue
        ks = np.concatenate([kp[p] for p in pt[s]], axis=1)[:, :tl]
        vs = np.concatenate([vp[p] for p in pt[s]], axis=1)[:, :tl]
        sc = np.einsum("hd,htd->ht", q[s], ks) / np.sqrt(D)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref[s] = np.einsum("ht,htd->hd", p, vs)
    return ref


# (head_dim, page): one token a row; two (the GPT-2 case in small); four
GEOMETRIES = [(8, 4), (64, 4), (32, 8)]


def _dense(by_token):
    """A pool given by token, (..., page, D), as the program holds it."""
    page, D = by_token.shape[-2:]
    pack = pk.kv_pack(page, D)
    return by_token.reshape(by_token.shape[:-2] + (page // pack, pack * D))


def test_kv_pack():
    assert [pk.kv_pack(page, D) for D, page in GEOMETRIES] == [1, 2, 4]
    assert pk.kv_pack(16, 64) == 2 and pk.kv_pack(16, 128) == 1
    assert pk.kv_pack(16, 96) == 1 and pk.kv_pack(3, 64) == 1


def _both_entries(q, kv, layer, pt, lens):
    """The six-axis entry's result, after holding the public 4-D entry to
    the same numbers (one body, two ways in)."""
    out = np.asarray(pk.decode_attention_pool(q, _dense(kv), layer, pt,
                                              lens))
    out4 = np.asarray(pk.decode_attention(q, kv[layer, 0], kv[layer, 1],
                                          pt, lens, use_pallas=True))
    live = lens > 0
    assert np.array_equal(out[live], out4[live])
    assert np.isfinite(out).all() and np.isfinite(out4).all()
    return out


@pytest.mark.parametrize("D,page", GEOMETRIES)
@pytest.mark.parametrize("layer", range(LAYERS))
def test_decode_attention_takes_the_whole_pool(layer, D, page):
    rs = np.random.RandomState(layer)
    S, nH, MP, P = 3, 2, 3, 10
    q = rs.randn(S, nH, D).astype(np.float32)
    kv = rs.randn(LAYERS, 2, P, nH, page, D).astype(np.float32)  # K != V
    pt = rs.randint(0, P, (S, MP)).astype(np.int32)
    # a partial page, every page full, an inactive slot
    lens = np.array([page + 1, MP * page, 0], np.int32)
    ref = _attention_reference(q, kv[layer, 0], kv[layer, 1], pt, lens)
    out = _both_entries(q, kv, layer, pt, lens)
    assert np.abs(out[:2] - ref[:2]).max() < 1e-5


def _scattered_table(rs, lens, page, MP, P):
    """Live pages drawn without order from pages 1..P-1, every dead entry
    on trash page 0."""
    pt = np.zeros((len(lens), MP), np.int32)
    free = 1 + rs.permutation(P - 1)
    at = 0
    for s, tl in enumerate(lens):
        n = -(-int(tl) // page)
        pt[s, :n] = free[at:at + n]
        at += n
    return pt


# a cell takes G pages at a time: 3 here, of a table of 8 (not a multiple)
GROUP, TABLE = 3, 8


@pytest.mark.parametrize("D,page", GEOMETRIES)
@pytest.mark.parametrize("case", ["page_edges", "group_edges",
                                  "full_one_none"])
def test_decode_attention_walks_live_pages_in_groups(case, D, page,
                                                     monkeypatch):
    monkeypatch.setattr(pk, "_DECODE_CELL_TOKENS", GROUP * page)
    nH, MP = 2, TABLE
    rows, lanes = _dense(np.zeros((page, D))).shape
    assert pk._decode_pages_per_cell(nH, rows, lanes, D, 4, MP) == GROUP
    lens = {
        # on, one before and one after a page boundary
        "page_edges": [page, page - 1, page + 1, 2 * page, 2 * page + 1],
        # the same round a group boundary, and round the table's last,
        # shorter group
        "group_edges": [GROUP * page, GROUP * page - 1, GROUP * page + 1,
                        2 * GROUP * page, 2 * GROUP * page + 1],
        # the full context beside one token beside an inactive slot
        "full_one_none": [MP * page, 1, 0, MP * page - 1, 0],
    }[case]
    lens = np.array(lens, np.int32)
    S, P = len(lens), 1 + len(lens) * MP
    rs = np.random.RandomState(D + page)
    q = rs.randn(S, nH, D).astype(np.float32)
    kv = rs.randn(LAYERS, 2, P, nH, page, D).astype(np.float32)
    kv[:, :, 0] = 1e3           # the trash page: loud if it is attended
    pt = _scattered_table(rs, lens, page, MP, P)
    ref = _attention_reference(q, kv[1, 0], kv[1, 1], pt, lens)
    out = _both_entries(q, kv, 1, pt, lens)
    live = lens > 0
    assert np.abs(out[live] - ref[live]).max() < 1e-5
    # the live slots do not feel their neighbours: alone they read the same
    alone = np.asarray(pk.decode_attention_pool(
        q[:1], _dense(kv), 1, pt[:1], lens[:1]))
    assert np.abs(alone[0] - out[0]).max() < 1e-6


def test_decode_attention_one_group_holds_the_whole_table():
    """With the rule's own numbers these small tables are one group, and
    a table shorter than a group clamps it."""
    rs = np.random.RandomState(4)
    D, page, nH, MP, P = 64, 4, 3, 5, 12
    lens = np.array([MP * page, 7, 0, 1], np.int32)
    rows, lanes = _dense(np.zeros((page, D))).shape
    assert pk._decode_pages_per_cell(nH, rows, lanes, D, 4, MP) == MP
    q = rs.randn(len(lens), nH, D).astype(np.float32)
    kv = rs.randn(LAYERS, 2, P, nH, page, D).astype(np.float32)
    pt = _scattered_table(rs, lens, page, MP, P)
    ref = _attention_reference(q, kv[2, 0], kv[2, 1], pt, lens)
    out = _both_entries(q, kv, 2, pt, lens)
    assert np.abs(out - ref)[lens > 0].max() < 1e-5


@pytest.mark.parametrize("name,H,rows,lanes,D,itemsize,n_pages", [
    ("gpt2_small", 12, 8, 128, 64, 4, 64),      # a 49 KB page, 16 tokens
    ("page_262KB", 32, 16, 128, 128, 4, 64),    # H = 32, D = 128
    ("bf16_pool", 12, 8, 128, 64, 2, 64),
    ("short_table", 12, 8, 128, 64, 4, 3),
    ("huge_page", 64, 64, 128, 128, 4, 64),     # one page over the budget
])
def test_pages_per_cell_follows_the_rule(name, H, rows, lanes, D, itemsize,
                                         n_pages):
    """G is the most pages that fit the VMEM budget (K and V twice in the
    pool's dtype, the scores once in float32), the token cap and the
    table; one at least; shapes alone decide it."""
    G = pk._decode_pages_per_cell(H, rows, lanes, D, itemsize, n_pages)
    tokens = rows * (lanes // D)

    def held(g):
        return g * H * rows * lanes * (4 * itemsize + 4)

    def fits(g):
        return (held(g) <= pk._DECODE_VMEM_BUDGET
                and g * tokens <= pk._DECODE_CELL_TOKENS and g <= n_pages)

    assert 1 <= G <= n_pages
    assert fits(G) or G == 1
    assert not fits(G + 1)
    # what the chip's sweep chose (PERF.md, PR 34), and what the budget
    # leaves of it for a page five times the size
    assert G == {"gpt2_small": 8, "page_262KB": 2, "short_table": 3,
                 "huge_page": 1}.get(name, G)


# -- (b) kv_write ----------------------------------------------------------

@pytest.mark.parametrize("D,page", GEOMETRIES)
@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("case", ["distinct", "trash"])
def test_kv_write_changes_exactly_its_rows(layer, case, D, page):
    rs = np.random.RandomState(7 + layer)
    S, nH, P = 5, 2, 9
    kv = rs.randn(LAYERS, 2, P, nH, page, D).astype(np.float32)
    k = rs.randn(S, nH, D).astype(np.float32)
    v = rs.randn(S, nH, D).astype(np.float32)
    if case == "distinct":
        phys = np.array([3, 7, 1, 8, 5], np.int32)
        off = np.array([0, 3, 2, 2, 1], np.int32)
    else:       # slots 1, 3, 4 inactive: all on trash page 0, two collide
        phys = np.array([3, 0, 6, 0, 0], np.int32)
        off = np.array([1, 2, 3, 2, 0], np.int32)
    out = np.asarray(pk.kv_write(_dense(kv), layer, k, v, phys, off))
    assert out.shape == _dense(kv).shape and out.dtype == kv.dtype
    out = out.reshape(kv.shape)
    touched = np.zeros(kv.shape, bool)
    for s in range(S):
        touched[layer, :, phys[s], :, off[s], :] = True
        # a live slot's row holds its K and V, bit for bit.  Slots share a
        # page only on trash page 0, where they overwrite one another in
        # any order (a later cell may write back the page as it was
        # fetched): a row there holds what one of them wrote, or stays
        rivals = [r for r in range(S)
                  if (phys[r], off[r]) == (phys[s], off[s])]
        assert phys[s] == 0 or rivals == [s]
        for at, new in ((0, k), (1, v)):
            row = out[layer, at, phys[s], :, off[s]]
            held = [new[r] for r in rivals]
            if phys[s] == 0:
                held.append(kv[layer, at, 0, :, off[s]])
            assert any(np.array_equal(row, h) for h in held), (s, at)
    assert touched.sum() == len({(p, o) for p, o in zip(phys, off)}) \
        * 2 * nH * D
    assert np.array_equal(out[~touched], kv[~touched])


# -- (c), (d) the step ------------------------------------------------------

VOCAB, T = 61, 16


@pytest.fixture(scope="module", params=[(32, 4), (128, 2)],
                ids=["head_dim8", "head_dim64"])
def cfg_and_params(request):
    hidden, heads = request.param
    cfg = DecodeConfig(VOCAB, LAYERS, hidden, heads, T, page_size=4,
                       max_seqs=3)
    return cfg, init_decode_params(cfg, seed=5)


def _pool_touches(jaxpr, page_shape, min_size, inside=""):
    """(primitive, where) of every equation, at any depth outside the
    Pallas kernels' own bodies, with a pool-sized operand or result: one
    that ends in a page's ``(H, page, D)`` and holds at least one layer's
    keys."""
    def pool_sized(x):
        shape = tuple(getattr(getattr(x, "aval", None), "shape", ()))
        return shape[-3:] == page_shape and np.prod(shape) >= min_size

    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        # a jitted call (decode_attn's, one trace for all layers) hands
        # its operands through: what counts is what it holds
        if name != "jit" and any(
                pool_sized(x) for x in list(eqn.invars) + list(eqn.outvars)):
            found.append((name, inside))
        if name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _pool_touches(sub, page_shape, min_size,
                                           inside + "/" + name)
    return found


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation, inside jitted calls too."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn)
        elif eqn.primitive.name == "jit":
            calls += _pallas_calls(eqn.params["jaxpr"].jaxpr)
    return calls


def test_step_touches_the_pool_only_in_pallas_calls(cfg_and_params,
                                                    monkeypatch):
    cfg, params = cfg_and_params
    monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE", "1")
    prog = DecodeProgram(params, cfg, name="jaxpr")
    kv = prog.fresh_cache()
    closed = jax.make_jaxpr(prog._make_step_fn(count=False))(
        prog._params, kv, *prog._zero_step_args())
    one_layers_k = kv.size // (2 * cfg.num_layers)   # kv[i, 0] is pool-sized
    assert kv.shape == cfg.pool_shape() and kv.shape[-1] in (8, 128)
    page = kv.shape[-3:]
    touches = _pool_touches(closed.jaxpr, page, one_layers_k)
    assert {name for name, _ in touches} == {"pallas_call"}, touches
    # a write and a read a layer, each on the whole pool
    assert len(touches) == 2 * cfg.num_layers
    assert sorted({e.params["name"] for e in _pallas_calls(closed.jaxpr)}) \
        == ["decode_attn", "kv_write"]
    # GC307 still knows it for a decode step (the aliased write)
    from mxnet_tpu.analysis.graphcheck import is_decode_shaped
    assert is_decode_shaped(closed)
    # and the XLA formulation is what it was: slices, scatters, gathers
    monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE", "0")
    closed = jax.make_jaxpr(prog._make_step_fn(count=False))(
        prog._params, kv, *prog._zero_step_args())
    by_token = (cfg.heads, cfg.page_size, cfg.head_dim)
    assert "scatter" in {n for n, _ in
                         _pool_touches(closed.jaxpr, by_token, one_layers_k)}


def _teacher_forced(prog, toks):
    """Feed ``toks`` (steps, S) through the step; (next tokens, logits)."""
    cfg = prog.config
    S = cfg.max_seqs
    table = np.zeros((S, cfg.pages_per_seq), np.int32)
    for s in range(S - 1):          # the last slot stays inactive
        table[s] = 1 + s * cfg.pages_per_seq + np.arange(cfg.pages_per_seq)
    active = np.arange(S) < S - 1
    kv = prog.fresh_cache()
    nxt, logits = [], []
    for t, tok in enumerate(toks):
        pos = np.full(S, t % cfg.max_seq_len, np.int32)
        if t == cfg.max_seq_len:    # contexts are full: start them again
            kv = prog.fresh_cache()
        n, lg, kv = prog.step(
            kv, tok, pos, np.where(active, pos + 1, 0).astype(np.int32),
            np.where(active, table[np.arange(S), pos // cfg.page_size], 0),
            np.where(active, pos % cfg.page_size, 0).astype(np.int32),
            table)
        nxt.append(np.asarray(n)[active])
        logits.append(np.asarray(lg)[active])
    return np.stack(nxt), np.stack(logits)


def test_pallas_step_agrees_with_xla_step(cfg_and_params, monkeypatch):
    cfg, params = cfg_and_params
    toks = np.random.RandomState(11).randint(
        0, VOCAB, (40, cfg.max_seqs)).astype(np.int32)
    got = {}
    for knob in ("1", "0"):
        monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE", knob)
        prog = DecodeProgram(params, cfg, name="knob" + knob)
        got[knob] = _teacher_forced(prog, toks)
        assert prog.trace_count == 1
    assert np.array_equal(got["1"][0], got["0"][0])
    assert np.abs(got["1"][1] - got["0"][1]).max() < 1e-5


# -- one host operand a step ---------------------------------------------

def _latent_program(chunk=32):
    from test_mla_decode import _cfg, _program
    return _program(_cfg(), chunk=chunk)[0]


def _gpt2_program():
    cfg = DecodeConfig(VOCAB, LAYERS, 32, 4, T, page_size=4, max_seqs=3)
    return DecodeProgram(init_decode_params(cfg, seed=5), cfg, name="packed")


def _gpt2_steps(prog):
    """Two steps' separate operands: slots 0 and 1 live at mixed lengths
    (slot 0 on a page's last row, then on the next page's first; its second
    token the first step's own), slot 2 inactive."""
    c = prog.config
    S, page = c.max_seqs, c.page_size
    table = np.zeros((S, c.pages_per_seq), np.int32)
    table[0, :3] = [5, 2, 7]
    table[1, :2] = [3, 6]
    steps = []
    for t in range(2):
        pos = np.array([page - 1 + t, t, 0], np.int32)
        live = np.array([1, 1, 0], np.int32)
        tokens = np.array([-1 if t else 11, 7 + t, 0], np.int32)
        steps.append((tokens, pos * live, (pos + 1) * live,
                      table[np.arange(S), pos // page] * live,
                      (pos % page) * live, table))
    return steps


def _latent_steps(prog):
    """Two steps of the many-token program: slot 0 decodes (its second
    token the first step's own), slot 1 takes 20 prompt rows of a budget of
    32 (a partly dead chunk) and then 12 more over a page's edge, yielding
    its first token; slot 2 and 3 idle."""
    c = prog.config
    S, C, page = c.max_seqs, c.prefill_tokens_per_step, c.page_size
    R, block = S + C, prog.chunk_block
    table = np.zeros((S, c.pages_per_seq), np.int32)
    table[0, :2] = [9, 4]
    table[1, :5] = [5, 2, 11, 3, 6]
    steps, done = [], 0
    for t, n in enumerate((20, 12)):
        tokens = np.zeros(R, np.int32)
        positions = np.full(R, -1, np.int32)
        phys = np.zeros(R, np.int32)
        off = np.zeros(R, np.int32)
        row_slot = np.zeros(R, np.int32)
        row_slot[:S] = np.arange(S)
        seq_lens = np.zeros(S, np.int32)
        out_row = np.arange(S, dtype=np.int32)
        tokens[0], positions[0] = (-1 if t else 13), t
        phys[0], off[0], seq_lens[0] = table[0, t // page], t % page, t + 1
        pos = done + np.arange(n)
        rows = S + np.arange(n)
        tokens[rows], positions[rows] = (3 + 5 * pos) % c.vocab_size, pos
        phys[rows], off[rows] = table[1, pos // page], pos % page
        row_slot[S:S + -(-n // block) * block] = 1
        done += n
        seq_lens[1] = done
        if t:
            out_row[1] = rows[-1]
        steps.append((tokens, positions, seq_lens, phys, off, table,
                      row_slot, out_row))
    return steps


@pytest.mark.parametrize("family", ["transformer_lm", "sarvam_mla"])
def test_packed_step_equals_the_separate_operands(family):
    """``step()`` hands the runtime ONE host vector and cuts it apart on the
    device; tokens, logits and pool are to the bit what the traced function
    gives when each operand is an argument of its own."""
    prog, steps = ((_gpt2_program(), _gpt2_steps) if family == "transformer_lm"
                   else (_latent_program(), _latent_steps))
    steps = steps(prog)
    assert [f[0] for f in prog._operands.fields] == [
        "tokens", "positions", "seq_lens", "phys", "off", "page_table",
        "row_slot", "out_row"][:len(steps[0])]
    assert prog._operands.size == sum(a.size for a in steps[0])
    separate = jax.jit(prog._make_step_fn(count=False))
    kv_a, kv_b = prog.fresh_cache(), prog.fresh_cache()
    prev_a = prev_b = None
    trash = 2 if family == "transformer_lm" else 1      # the pages' axis
    for ops in steps:
        out_a = prog.step(kv_a, *ops[:6], prev_a, *ops[6:])
        out_b = separate(prog._params, kv_b, *ops[:6],
                         prog._no_prev_tok if prev_b is None else prev_b,
                         *ops[6:])
        (prev_a, lg_a, kv_a), (prev_b, lg_b, kv_b) = out_a[:3], out_b[:3]
        assert np.array_equal(np.asarray(prev_a), np.asarray(prev_b))
        assert np.array_equal(np.asarray(lg_a), np.asarray(lg_b))
        # all but the trash page, where dead rows land
        pool_a = np.moveaxis(np.asarray(kv_a, np.float32), trash, 0)[1:]
        pool_b = np.moveaxis(np.asarray(kv_b, np.float32), trash, 0)[1:]
        assert np.array_equal(pool_a, pool_b) and pool_a.any()
        for x, y in zip(out_a[3:], out_b[3:]):
            assert np.array_equal(np.asarray(x), np.asarray(y))
    assert np.isfinite(np.asarray(lg_a)[:2]).all()
    assert prog.trace_count == 1
    with pytest.raises(mxnet_tpu.base.MXNetError):
        prog._operands.pack(*steps[0][:5])


@pytest.mark.parametrize("family", ["transformer_lm", "sarvam_mla"])
def test_engine_forward_and_step_share_one_executable(family):
    """The engine (which hands ``prev_tok`` back as it came), ``forward``
    and a direct ``step()`` without one all go through the one jitted call:
    one trace, one executable."""
    from mxnet_tpu.serving.decode import DecodeEngine
    prog = _gpt2_program() if family == "transformer_lm" \
        else _latent_program(chunk=16)
    c = prog.config
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        out = eng.generate(np.arange(5) % c.vocab_size, max_new_tokens=4)
        st = eng.stats()
    assert len(out) == 4
    assert st["decode"]["host_operands_per_step"] == 1.0
    nxt = prog.forward(np.arange(c.max_seqs * c.forward_len).reshape(
        c.max_seqs, c.forward_len) % c.vocab_size)[0]
    assert nxt.shape == (c.max_seqs, 1)
    steps = (_gpt2_steps if family == "transformer_lm"
             else _latent_steps_of_slots)(prog)
    kv, prev = prog.fresh_cache(), None
    for ops in steps:               # without prev_tok, then with the device's
        res = prog.step(kv, *ops, prev)
        prev, kv = res[0], res[2]
    assert prog.handed_over(None) == (1, len(prog._params) + 2)
    assert prog.handed_over(prev) == (1, len(prog._params) + 2)
    assert prog.handed_over(np.asarray(prev)) == (2, len(prog._params) + 1)
    assert prog.trace_count == 1
    assert prog._jit_step._cache_size() == 1


def _latent_steps_of_slots(prog):
    """One row a slot (the one-token signature): the chunk rides dead."""
    c = prog.config
    S, page = c.max_seqs, c.page_size
    table = np.zeros((S, c.pages_per_seq), np.int32)
    table[:, 0] = 1 + np.arange(S)
    steps = []
    for t in range(2):
        pos = np.full(S, t, np.int32)
        steps.append((np.full(S, -1 if t else 3, np.int32), pos, pos + 1,
                      table[:, 0].copy(), pos % page, table))
    return steps


# -- the 64-slot step, compiled for a described v5e ------------------------

@pytest.fixture(scope="module")
def v5e_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return topo.devices[0]


@pytest.mark.parametrize("S", [64, 32])
def test_64_slot_step_compiles_for_the_v5e(v5e_chip, monkeypatch, S):
    """GPT-2 small at 64 slots (and at the serve cell's 32), context 1024,
    page 16.  Before PR 30 the TPU compiler refused this step ("Used
    18.26G of 15.75G hbm": a padded copy of the pool re-laid for the
    scatter).  Now the plan is the arguments: nothing pool-sized but the
    pool, and the pool lane-dense; ``decode_attn`` leaves it in HBM and
    copies a slot's live pages out of it, so it brings no temporary."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE", "1")
    monkeypatch.setattr(pk, "_interpret", lambda *a: False)   # Mosaic
    cfg = DecodeConfig(50257, 12, 768, 12, 1024, page_size=16, max_seqs=S)
    # the program object needs host arrays to exist; zeros do for a compile
    weights = {k: np.zeros(shape, np.float32)
               for k, shape in decode_param_shapes(cfg).items()}
    prog = DecodeProgram(weights, cfg, name="aot%d" % S)
    on = SingleDeviceSharding(v5e_chip)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=on)

    pool = cfg.pool_shape()
    compiled = jax.jit(prog._make_step_fn(count=False),
                       donate_argnums=(1,)).lower(
        {k: sds(v.shape, jnp.float32) for k, v in weights.items()},
        sds(pool, jnp.float32), sds((S,)), sds((S,)), sds((S,)), sds((S,)),
        sds((S,)), sds((S, cfg.pages_per_seq))).compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    planned = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print("%d-slot decode step: planned %.2f GB, temporaries %.3f GB, pool "
          "%.2f GB of data" % (S, planned / 1e9, ma.temp_size_in_bytes / 1e9,
                               4 * np.prod(pool) / 1e9))
    # every instruction with a pool-sized result is the entry parameter or
    # one of the kernels that write it in place
    pool_type = r"f32\[%s\]" % ",".join(map(str, pool))
    makers = re.findall(r"= %s\S* ([\w\-]+)\(" % pool_type, text)
    assert makers and set(makers) <= {"parameter", "custom-call"}, makers
    assert makers.count("custom-call") == cfg.num_layers
    mosaic = re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target='
                        r'"tpu_custom_call"', text)
    for kernel in ("kv_write", "decode_attn"):
        assert sum(kernel in c for c in mosaic) == cfg.num_layers, mosaic
    assert len(mosaic) == 2 * cfg.num_layers, mosaic    # and no third
    assert ma.temp_size_in_bytes < 0.5e9
    # the pool arrives row-major and unpadded: 4.83 GB at 64 slots, all of
    # it data
    assert ma.alias_size_in_bytes == 4 * np.prod(pool)
    assert planned < 8e9


def test_chunked_step_compiles_for_the_v5e(v5e_chip, monkeypatch):
    """The serve cell's many-token step (32 slots and the 96 prompt rows
    the rule derives for them on a v5e): the pool is still written and read
    by Mosaic kernels alone, now three a layer (``kv_write`` over all 128
    rows, ``decode_attn`` over the slots' own, ``chunk_attn`` over the
    chunk), each lowered once for the twelve layers, and nothing pool-sized
    is made but by ``kv_write`` in place."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE", "1")
    monkeypatch.setattr(pk, "_interpret", lambda *a: False)   # Mosaic
    S, C = 32, 96
    cfg = DecodeConfig(50257, 12, 768, 12, 1024, page_size=16, max_seqs=S,
                       prefill_tokens_per_step=C)
    weights = {k: np.zeros(shape, np.float32)
               for k, shape in decode_param_shapes(cfg).items()}
    prog = DecodeProgram(weights, cfg, name="aot-chunk")
    on = SingleDeviceSharding(v5e_chip)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=on)

    R = S + C
    lowered = jax.jit(prog._make_step_fn(count=False),
                      donate_argnums=(1,)).lower(
        {k: sds(v.shape, jnp.float32) for k, v in weights.items()},
        sds(cfg.pool_shape(), jnp.float32), sds((R,)), sds((R,)), sds((S,)),
        sds((R,)), sds((R,)), sds((S, cfg.pages_per_seq)), sds((S,)),
        sds((R,)), sds((S,)))
    assert lowered.as_text().count("tpu_custom_call") == 3
    compiled = lowered.compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    pool_type = r"f32\[%s\]" % ",".join(map(str, cfg.pool_shape()))
    makers = re.findall(r"= %s\S* ([\w\-]+)\(" % pool_type, text)
    assert set(makers) == {"parameter", "custom-call"}, makers
    assert makers.count("custom-call") == cfg.num_layers
    mosaic = re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target='
                        r'"tpu_custom_call"', text)
    for kernel in ("kv_write", "decode_attn", "chunk_attn"):
        assert sum(kernel in c for c in mosaic) == cfg.num_layers, mosaic
    assert len(mosaic) == 3 * cfg.num_layers, mosaic
    assert ma.temp_size_in_bytes < 0.1e9
    assert ma.alias_size_in_bytes == 4 * np.prod(cfg.pool_shape())
