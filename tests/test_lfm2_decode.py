"""The hybrid decode program (``serving/decode.py::HybridDecodeProgram`` over
``models/lfm2_moe.py``: gated short convolutions with a per-slot state beside
grouped-query attention over a K/V pool of the attention layers) at a tiny
size on the CPU: the three pool kernels with query groups against their XLA
formulations, the segmented convolution against the full-sequence one, the
held expert layer holding every expert against the dense routing formula,
the program against the plain reference (``benchmark/refs/lfm2_moe.py``)
through chunked prefill, decoding and a reused slot, and the engine's
contract over the many-token step."""
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_CFG = os.path.join(ROOT, "benchmark", "tests", "tiny_lfm2_moe", "bench",
                        "configs", "tiny-lfm2.json")


def _cfg(**over):
    with open(TINY_CFG) as f:
        cfg = json.load(f)
    cfg.update(over)
    return cfg


def _ref():
    from benchmark.lib import harness
    return harness.load_module(os.path.join(ROOT, "benchmark", "refs",
                                            "lfm2_moe.py"))


def _program(cfg, seed=3, slots=4, chunk=32, page=8):
    from mxnet_tpu.models import lfm2_moe
    from mxnet_tpu.serving.decode import DecodeConfig, HybridDecodeProgram
    weights = _ref().make_weights(cfg, seed)
    dc = DecodeConfig(cfg["vocab_size"], cfg["num_hidden_layers"],
                      cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["n_positions"], page_size=page, max_seqs=slots,
                      family="lfm2_moe", dtype=cfg["serving"]["dtype"],
                      prefill_tokens_per_step=chunk,
                      model=lfm2_moe.model_of(cfg),
                      kv_heads=cfg["num_key_value_heads"])
    return HybridDecodeProgram(weights, dc, name="t"), weights


# -- the pool kernels with a query group a key/value head -----------------------

@pytest.mark.parametrize("rep,dtype,tol", [(4, "bfloat16", 2e-2),
                                           (1, "float32", 2e-6)])
def test_pool_kernels_equal_their_xla_formulations(rep, dtype, tol):
    """``kv_write``, ``decode_attn`` and ``chunk_attn`` in the interpreter
    against the XLA formulation: rep 4 over a bfloat16 pool, rep 1 (GPT-2's
    call) over a float32 one.  Slot 0 decodes at 13 positions, slot 1 is
    idle, slot 2 decodes at 40; a chunk holds slot 2's rows 20-35 and slot
    0's rows 0-2 in two blocks."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    rs = np.random.default_rng(0)
    L, P, h_kv, D, page, S = 2, 13, 2, 64, 16, 3
    H = rep * h_kv
    pack = pk.kv_pack(page, D)
    pool = jnp.asarray(rs.normal(size=(L, 2, P, h_kv, page // pack,
                                       pack * D)), dtype)
    table = rs.permutation(np.arange(1, P))[:S * 4].reshape(S, 4) \
        .astype(np.int32)
    # the write: a run of one page, a dead row on the trash page, another page
    k = rs.normal(size=(5, h_kv, D)).astype(np.float32)
    phys = np.array([table[0, 0]] * 3 + [0, table[2, 2]], np.int32)
    off = np.array([4, 5, 6, 0, 15], np.int32)
    wrote = pk.kv_write(pool, 1, k, 2 * k, phys, off)
    plain = pk.kv_write(pool, 1, k, 2 * k, phys, off, use_pallas=False)
    assert wrote.dtype == pool.dtype
    a, b = np.asarray(wrote, np.float32), np.asarray(plain, np.float32)
    assert np.array_equal(a[:, :, 1:], b[:, :, 1:])
    by_token = a.reshape(L, 2, P, h_kv, page, D)
    np.testing.assert_array_equal(
        by_token[1, 1, table[2, 2], :, 15],
        np.asarray(jnp.asarray(2 * k[4], dtype), np.float32))
    seq_lens = np.array([13, 0, 40], np.int32)
    q = rs.normal(size=(S, H, D)).astype(np.float32)
    for layer in (0, 1):
        got = np.asarray(pk.decode_attention_pool(q, wrote, layer, table,
                                                  seq_lens), np.float32)
        by = jnp.asarray(wrote).reshape(L, 2, P, h_kv, page, D)[layer]
        want = np.asarray(pk._decode_attn_xla(q, by[0], by[1], table,
                                              seq_lens, D ** -0.5))
        assert np.isfinite(got).all()
        assert np.abs(got - want)[seq_lens > 0].max() < tol
    C = 32
    qc = rs.normal(size=(C, H, D)).astype(np.float32)
    row_slot = np.array([2] * 16 + [0] * 16, np.int32)
    limit = np.array(list(range(21, 37)) + [1, 2, 3] + [0] * 13, np.int32)
    args = (qc, wrote, 1, table, row_slot, limit)
    got = np.asarray(pk.chunk_attention(*args, use_pallas=True), np.float32)
    want = np.asarray(pk.chunk_attention(*args, use_pallas=False), np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want)[limit > 0].max() < tol


def test_grouped_query_pages_are_walked_in_larger_groups():
    """A group's pages serve its query heads at once: G is taken at
    ``_GQA_CELL_TOKENS``, 8 pages of 64 at the cell's pool (8 heads, bf16),
    where GPT-2's rule keeps its 8 of 16."""
    from mxnet_tpu.ops import pallas_kernels as pk
    assert pk._decode_pages_per_cell(12, 8, 128, 64, 4, 64) == 8
    assert pk._decode_pages_per_cell(
        8, 32, 128, 64, 2, 64, cell_tokens=pk._GQA_CELL_TOKENS) == 8


def test_grouped_product_row_tile_follows_the_rows_a_group_holds():
    """``_gmm_tiling``, the backward products' tiling and the forward's
    where they are not weight-bound (``tests/test_grouped_matmul_tiling.py``
    has the forward rule): 2,048 sorted rows over 32 experts take 256-row
    tiles, not 512; a training step's 10,240 rows and more over 16 experts
    512; 768-3,584 over 16: 256, 512, 512, 512; a tile divides the rows."""
    from mxnet_tpu.ops import pallas_kernels as pk
    assert pk._gmm_tiling(2048, 32) == (256, 1024, 1024)
    for m in (10240, 20480, 65536):
        assert pk._gmm_tiling(m, 16) == (512, 1024, 1024)
    assert [pk._gmm_tiling(m, 16)[0] for m in (768, 1536, 3072, 3584)] \
        == [256, 512, 512, 512]
    assert pk._gmm_tiling(100, 1)[0] == 4


# -- the segmented convolution ----------------------------------------------------

def _random_layout(rs, S, R, L):
    """A step's rows as ``_build_rows`` lays them out, at random: some slots
    a decoding row, others a run of consecutive chunk rows from a random
    position, blocks padded with dead rows.  Returns (positions,
    row_slot); the caller renumbers each slot's positions."""
    block = 4
    positions = np.full(R, -1, np.int32)
    row_slot = np.zeros(R, np.int32)
    row_slot[:S] = np.arange(S)
    at = S
    for s in rs.permutation(S):
        start = int(rs.integers(0, 9))
        if rs.random() < 0.4:
            positions[s] = start
        elif rs.random() < 0.8 and at < R:
            n = int(rs.integers(1, min(7, R - at) + 1))
            positions[at:at + n] = start + np.arange(n)
            whole = min(-(-n // block) * block, R - at)
            row_slot[at:at + whole] = s
            at += whole
    return positions, row_slot


def test_segmented_convolution_is_the_full_sequence_one():
    """Steps of random row layouts over 5 slots, the state carried between
    them, give every row the convolution of its slot's whole sequence: the
    carried rows where the step does not hold them, zeros before position
    0 (so a slot that starts again reads nothing its last sequence left)."""
    import jax.numpy as jnp
    from mxnet_tpu.models.lfm2_moe import segmented_conv
    rs = np.random.default_rng(4)
    S, R, L, d = 5, 24, 3, 6
    taps = rs.normal(size=(d, L)).astype(np.float32)
    state = jnp.asarray(rs.normal(size=(S, L, d)), jnp.float32)  # stale
    seqs = {s: {} for s in range(S)}     # slot -> position -> v
    nxt = np.zeros(S, np.int32)           # each slot's next position
    for step in range(40):
        positions, row_slot = _random_layout(rs, S, R, L)
        # each slot's rows continue its sequence, or start it again
        for s in range(S):
            mine = np.flatnonzero((row_slot == s) & (positions >= 0))
            if not mine.size:
                continue
            if rs.random() < 0.15:
                nxt[s] = 0
                seqs[s] = {}
            positions[mine] = nxt[s] + np.arange(mine.size)
            nxt[s] += mine.size
        v = rs.normal(size=(R, d)).astype(np.float32)
        z, state = segmented_conv(jnp.asarray(v), state, jnp.asarray(taps),
                                  jnp.asarray(positions),
                                  jnp.asarray(row_slot))
        z = np.asarray(z)
        for r in np.flatnonzero(positions >= 0):
            seqs[row_slot[r]][positions[r]] = v[r]
        for r in np.flatnonzero(positions >= 0):
            s, p = row_slot[r], positions[r]
            want = sum(taps[:, L - 1 - lag] * seqs[s].get(p - lag, 0.0)
                       for lag in range(L))
            np.testing.assert_allclose(z[r], want, rtol=1e-5, atol=1e-5)


# -- the expert layer, every expert held --------------------------------------------

def test_held_layer_with_every_expert_is_the_dense_routing_formula():
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import moe_ffn_held
    ref = _ref()
    cfg = _cfg()
    p = {k: jnp.asarray(v) for k, v in ref.make_weights(cfg, 7).items()}
    pre = "l3_moe_"
    h = jnp.asarray(np.random.default_rng(1).normal(
        size=(24, cfg["hidden_size"])), jnp.float32)
    whole = np.asarray(ref.experts(p, pre, h, cfg))
    out, load = moe_ffn_held(
        h, p[pre + "router_weight"], jnp.zeros(8), None,
        tuple(p[pre + "expert_" + w] for w in ("w1", "w3", "w2")),
        num_experts=8, first_expert=0, top_k=2, route_norm=True,
        route_scale=cfg["routed_scaling_factor"])
    assert float(load.sum()) == 24 * 2
    assert np.abs(np.asarray(out) - whole).max() < 1e-5 * np.abs(whole).max()


# -- the program against the reference ----------------------------------------------

def _step_arrays(prog, table, entries):
    """A step's arrays for ``entries`` [(slot, ids, start, as_chunk)]: a
    chunk's rows start a block of their own, a decoding slot has its row."""
    c = prog.config
    S, R, block, page = c.max_seqs, prog.rows, prog.chunk_block, c.page_size
    tokens = np.zeros(R, np.int32)
    positions = np.full(R, -1, np.int32)
    phys = np.zeros(R, np.int32)
    off = np.zeros(R, np.int32)
    row_slot = np.zeros(R, np.int32)
    row_slot[:S] = np.arange(S)
    seq_lens = np.zeros(S, np.int32)
    out_row = np.arange(S, dtype=np.int32)
    at = S
    for slot, ids, start, as_chunk in entries:
        n = len(ids)
        pos = start + np.arange(n)
        if as_chunk:
            rows = at + np.arange(n)
            whole = -(-n // block) * block
            row_slot[at:at + whole] = slot
            at += whole
            out_row[slot] = rows[-1]
        else:
            rows = np.array([slot])
        assert at <= R
        tokens[rows], positions[rows] = ids, pos
        phys[rows], off[rows] = table[slot, pos // page], pos % page
        seq_lens[slot] = start + n
    return tokens, positions, seq_lens, phys, off, table, None, row_slot, \
        out_row


def _plan(rs, vocab):
    """Four sequences over three slots: A (slot 1) in chunks of 1, 2, 3 and
    17 rows, then decoding; B (slot 2) a decoding row every step; D (slot
    3) in chunks of 10 and 4 beside A's; C in slot 1 after A, from position
    0 again.  Returns (sequences, steps of [(slot, name, start, n, chunk)])."""
    seqs = {name: rs.integers(0, vocab, n).astype(np.int32)
            for name, n in (("A", 40), ("B", 30), ("C", 12), ("D", 14))}
    steps = [[("A", 1, 0, 1, True), ("B", 2, 0, 1, False)],
             [("A", 1, 1, 2, True), ("B", 2, 1, 1, False),
              ("D", 3, 0, 10, True)],
             [("A", 1, 3, 3, True), ("B", 2, 2, 1, False),
              ("D", 3, 10, 4, True)],
             [("A", 1, 6, 17, True), ("B", 2, 3, 1, False)]]
    for j in range(17):
        steps.append([("A", 1, 23 + j, 1, False), ("B", 2, 4 + j, 1, False)])
    steps.append([("C", 1, 0, 5, True), ("B", 2, 21, 1, False)])
    for j in range(7):
        step = [("C", 1, 5 + j, 1, False)]
        if 22 + j < 30:
            step.append(("B", 2, 22 + j, 1, False))
        steps.append(step)
    return seqs, steps


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_chunked_prefill_decode_and_reuse_follow_the_reference(monkeypatch,
                                                               backend):
    """Float32 on both sides, seven layers (attention at 2 and 6): chunks of
    1, 2, 3 and 17 rows (a chunk boundary inside every convolution window),
    decoding rows beside chunk rows, two slots' chunks in one step, and a
    slot that starts a new sequence give, at every row that yields a token,
    the logits of the reference's full forward pass of that sequence."""
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE",
                       "1" if backend == "pallas" else "0")
    cfg = _cfg(num_hidden_layers=7, layer_types=[
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention"])
    prog, weights = _program(cfg)
    c = prog.config
    assert c.pool_shape()[:4] == (2, 2, c.pool_pages(), 2)
    table = np.zeros((c.max_seqs, c.pages_per_seq), np.int32)
    for s in range(c.max_seqs):
        table[s] = 1 + s * c.pages_per_seq + np.arange(c.pages_per_seq)
    rs = np.random.default_rng(5)
    seqs, steps = _plan(rs, cfg["vocab_size"])
    state = prog.fresh_cache()
    got = []                                # (sequence, position, logits)
    for step in steps:
        entries = [(slot, seqs[name][start:start + n], start, chunk)
                   for name, slot, start, n, chunk in step]
        _tok, lg, state, counts = prog.step(
            state, *_step_arrays(prog, table, entries))
        for name, slot, start, n, _chunk in step:
            got.append((name, start + n - 1, np.asarray(lg[slot])))
        assert int(counts[0]) == 2 * sum(n for *_r, n, _c in step) \
            * (cfg["num_hidden_layers"] - cfg["num_dense_layers"])
    assert prog.trace_count == 1
    ref = _ref()
    want = {}
    for name, ids in seqs.items():
        padded = np.zeros((1, cfg["n_positions"]), np.int32)
        padded[0, :len(ids)] = ids
        u = ref.final_hidden(weights, padded, cfg)[0]
        want[name] = np.asarray(u @ jnp.asarray(weights["tok_embed_weight"]).T)
    for name, at, lg in got:
        scale = np.abs(want[name][:len(seqs[name])]).max()
        assert np.abs(lg - want[name][at]).max() < 2e-4 * scale, (name, at)


# -- the engine over the many-token step ---------------------------------------------

def test_engine_serves_the_hybrid_family(monkeypatch):
    """Requests of mixed lengths through ``submit()`` -> ``result()``, more
    requests than slots so that slots are reused: one trace, counts equal to
    what was sent, the state's bytes in the stats, ``state_rows`` on the
    step spans, and every served token the reference's own choice."""
    import jax
    from mxnet_tpu.serving.decode import DecodeEngine
    cfg = _cfg()
    prog, _weights = _program(cfg, seed=9, chunk=16)
    noted = []

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
    lengths = ((5, 4), (23, 6), (40, 3), (1, 5), (17, 2), (9, 9), (33, 1),
               (2, 7))
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
    n_conv, S, L, d = 5, 4, 3, cfg["hidden_size"]
    assert dec["state_bytes"] == prog.state_bytes == n_conv * S * L * d * 4
    assert dec["pool_bytes"] == prog.cache_bytes == prog.state_bytes \
        + int(np.prod(prog.config.pool_shape())) * 4

    def total(key):
        return sum(a.get(key, 0) for a in noted)

    # every decoding row past position 0 reads its slot's state; a prompt's
    # first chunk reads none, a later chunk's first two rows do
    assert 0 < total("state_rows") <= dec["tokens_decoded"] \
        + 2 * dec["tokens_prefilled"]
    assert total("state_rows") >= sum(m for n, m in lengths if n + m > 1) \
        - len(lengths)
    assert total("expert_rows") > 0 and total("experts_touched") > 0
    gap, n, where = _ref().served_token_gap(cfg, 9, list(zip(prompts, served)),
                                            1)
    assert n == sum(m for _n, m in lengths)
    assert gap < 1e-4, where


def test_config_carries_key_value_heads_and_the_state(tmp_path):
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.serving import decode
    cfg = _cfg(serving={"dtype": "bfloat16"})
    prog, _w = _program(cfg)
    c = prog.config
    assert c.kv_heads == 2 and c.heads == 8
    assert c.describe().startswith("lfm2_moe bfloat16 L6 H128 heads8/2 ")
    assert c.to_meta()["kv_heads"] == 2
    state = prog.fresh_cache()
    assert set(state) == {"kv", "conv"}
    assert str(state["kv"].dtype) == str(state["conv"].dtype) == "bfloat16"
    assert state["conv"].shape == (5, 4, 3, 128)
    assert prog.cache_bytes == 2 * (int(np.prod(c.pool_shape()))
                                    + 5 * 4 * 3 * 128)
    # a config that states other key/value heads than the model is refused
    with pytest.raises(MXNetError, match="key/value heads"):
        decode.HybridDecodeProgram({}, decode.DecodeConfig(
            **dict(c.to_meta(), kv_heads=8)))
    path = prog.export(str(tmp_path / "hybrid.mxd"))
    back = decode.DecodeProgram.load(path)
    assert type(back) is decode.HybridDecodeProgram
    assert back.config.same_geometry(c)
    # GPT-2's config keeps one head count for queries and cache alike
    assert decode.DecodeConfig(96, 2, 32, 4, 16).kv_heads == 4
