"""Pallas kernel tests (interpret mode on the CPU backend; the same
pallas_call lowers to real TPU kernels on device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops.pallas_kernels import (fused_attention,
                                          fused_attention_bwd,
                                          fused_attention_fwd,
                                          two_bit_compress)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla", "pallas-kernel"])
def test_two_bit_compress_matches_formula(use_pallas):
    rs = np.random.RandomState(0)
    for shape in [(7,), (33, 5), (2, 3, 4)]:
        g = jnp.asarray(rs.normal(0, 1, shape).astype(np.float32))
        r = jnp.asarray(rs.normal(0, 0.3, shape).astype(np.float32))
        q, nr = two_bit_compress(g, r, threshold=0.5,
                                 use_pallas=use_pallas)
        comp = np.asarray(g) + np.asarray(r)
        want_q = np.where(comp >= 0.5, 0.5, np.where(comp <= -0.5, -0.5, 0.0))
        np.testing.assert_allclose(np.asarray(q), want_q, atol=1e-6)
        np.testing.assert_allclose(np.asarray(nr), comp - want_q, atol=1e-6)
        assert q.shape == shape and nr.shape == shape


def test_two_bit_error_feedback_accumulates():
    """Small gradients below threshold must eventually fire via the
    residual (the whole point of error feedback)."""
    g = jnp.full((16,), 0.2, jnp.float32)
    r = jnp.zeros((16,), jnp.float32)
    fired = 0.0
    for _ in range(5):
        q, r = two_bit_compress(g, r, threshold=0.5)
        fired += float(np.asarray(q).sum())
    # 5 steps x 0.2 = 1.0 per element; quantized emissions must track it
    assert fired > 0
    total = fired + float(np.asarray(r).sum())
    np.testing.assert_allclose(total, 16 * 1.0, rtol=1e-5)


def test_kvstore_compression_uses_fused_kernel():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("w", nd.zeros((8,)))
    kv.push("w", nd.array(np.full(8, 0.6, np.float32)))
    out = nd.zeros((8,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full(8, 0.5), atol=1e-6)


def _naive_attention(q, k, v, causal=False, scale=None):
    B, T, H, D = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = np.tril(np.ones((T, k.shape[1]), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_k", [512, 8],
                         ids=["one-k-block", "multi-k-block"])
def test_fused_attention_matches_naive(causal, block_k):
    """block_k=8 forces nk=4: the online-softmax carry (running max/sum
    renormalization across k blocks, causal block skipping) is on the
    line, not just the single-block degenerate path."""
    rs = np.random.RandomState(1)
    B, T, H, D = 2, 32, 2, 16
    q = jnp.asarray(rs.normal(0, 1, (B, T, H, D)).astype(np.float32))
    k = jnp.asarray(rs.normal(0, 1, (B, T, H, D)).astype(np.float32))
    v = jnp.asarray(rs.normal(0, 1, (B, T, H, D)).astype(np.float32))
    out = fused_attention(q, k, v, causal=causal, block_q=16,
                          block_k=block_k)
    want = _naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_fused_attention_single_block():
    rs = np.random.RandomState(2)
    q = jnp.asarray(rs.normal(0, 1, (1, 8, 1, 8)).astype(np.float32))
    out = fused_attention(q, q, q, block_q=128)  # bq clamps to T
    want = _naive_attention(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_fused_attention_op_flash_min_seq_attr():
    """Op-level flash dispatch: flash_min_seq=1 forces the Pallas flash
    forward + fused flash backward THROUGH the operator even at tiny T
    (the env default would route this to the plain einsum path).
    Covers the attr half of the MXNET_FLASH_MIN_SEQ resolution — the env
    half is frozen at import so it cannot silently change post-trace."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    rs = np.random.RandomState(3)
    B, T, H, D = 2, 16, 2, 8
    qh = rs.normal(0, 1, (B, T, H, D)).astype(np.float32)
    kh = rs.normal(0, 1, (B, T, H, D)).astype(np.float32)
    vh = rs.normal(0, 1, (B, T, H, D)).astype(np.float32)
    q, k, v = nd.array(qh), nd.array(kh), nd.array(vh)

    out = nd.contrib.fused_attention(q, k, v, flash_min_seq=1,
                                     block_q=8).asnumpy()
    want = np.asarray(_naive_attention(
        jnp.asarray(qh), jnp.asarray(kh), jnp.asarray(vh)))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)

    # backward rides the rematerializing custom vjp
    gq = nd.zeros((B, T, H, D))
    mx.autograd.mark_variables([q], [gq])
    with mx.autograd.record():
        o = nd.contrib.fused_attention(q, k, v, flash_min_seq=1, block_q=8)
        mx.autograd.backward([o])
    g = gq.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


# ---------------------------------------------------------------------------
# flash backward (round 6): recompute-free dQ/dK/dV from the saved lse
# ---------------------------------------------------------------------------

# (dtype, (B, T, H, D), (block_q, block_k), inner sub-tile, rtol, atol).
# The bfloat16 cases are the benchmark cell's arithmetic at a length that
# has live, diagonal and dead blocks for every kernel; the sub-tile cases
# walk several key sub-tiles inside each block, masked and not.
_VJP_CASES = {
    "sym": (np.float32, (2, 32, 2, 16), (16, 16), None, 1e-4, 1e-5),
    "multi-k": (np.float32, (2, 32, 2, 16), (16, 8), None, 1e-4, 1e-5),
    "multi-q": (np.float32, (2, 32, 2, 16), (8, 16), None, 1e-4, 1e-5),
    "sub-tiles": (np.float32, (1, 128, 2, 16), (32, 64), 16, 1e-4, 1e-5),
    "sub-tiles-wide-q": (np.float32, (1, 128, 2, 16), (64, 32), 8, 1e-4,
                         1e-5),
    "bf16-64x128": ("bfloat16", (2, 256, 2, 64), (64, 128), 32, 0.05,
                    0.02),
    "bf16-128x64": ("bfloat16", (2, 256, 2, 64), (128, 64), 32, 0.05,
                    0.02),
}


@pytest.fixture
def flash_sub(monkeypatch):
    """Shrink the kernels' inner key sub-tile (512 rows on the chip) so
    that a test-sized block holds several."""
    from mxnet_tpu.ops import pallas_kernels as pk

    def set_sub(rows):
        if rows is not None:
            monkeypatch.setattr(pk, "_FLASH_SUB_K", rows)
    return set_sub


def _f32(x):
    return jnp.asarray(np.asarray(x, np.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", list(_VJP_CASES))
def test_flash_backward_matches_einsum_vjp(causal, blocks, flash_sub):
    """The flash forward and dQ/dK/dV kernels against jax.vjp of the
    float32 einsum formulation, across block shapes that force the online
    accumulators (multi-k: several score tiles per dQ row; multi-q:
    several per dK/dV column), the causal block-skipping, the inner
    sub-tile walk and bfloat16 operands."""
    dtype, (B, T, H, D), (bq, bk), sub, rtol, atol = _VJP_CASES[blocks]
    flash_sub(sub)
    rs = np.random.RandomState(7)
    q, k, v, g = (jnp.asarray(rs.normal(0, 1, (B, T, H, D))
                              .astype(np.float32)).astype(dtype)
                  for _ in range(4))
    scale = float(1.0 / np.sqrt(D))

    out, lse = fused_attention_fwd(q, k, v, causal=causal,
                                   block_q=bq, block_k=bk)
    dq, dk, dv = fused_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                     block_q=bq, block_k=bk)
    want, vjp = jax.vjp(
        lambda a, b, c: _naive_attention(a, b, c, causal=causal,
                                         scale=scale),
        _f32(q), _f32(k), _f32(v))
    for got, ref in zip((out, dq, dk, dv), (want,) + vjp(_f32(g))):
        assert got.dtype == q.dtype
        np.testing.assert_allclose(_f32(got), np.asarray(ref),
                                   rtol=rtol, atol=atol)


def _kernel_dots(fn, *args):
    """Every ``dot_general`` inside the Pallas kernel bodies ``fn``
    traces, loops and branches included."""
    found = []

    def walk(jaxpr, in_kernel):
        for eqn in jaxpr.eqns:
            if in_kernel and eqn.primitive.name == "dot_general":
                found.append(eqn)
            inside = in_kernel or eqn.primitive.name == "pallas_call"
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple))
                            else [val]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, inside)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_products_run_in_the_input_dtype(dtype):
    """bfloat16 inputs: no product of the three kernels has a float32
    operand (that is several MXU passes a product on the v5e), and each
    accumulates in float32.  float32 inputs keep float32 products."""
    rs = np.random.RandomState(3)
    q, k, v, g = (jnp.asarray(rs.normal(0, 1, (1, 32, 2, 16))
                              .astype(np.float32)).astype(dtype)
                  for _ in range(4))

    def fwd_bwd(q, k, v, g):
        out, lse = fused_attention_fwd(q, k, v, causal=True, block_q=16,
                                       block_k=16)
        return fused_attention_bwd(q, k, v, out, lse, g, causal=True,
                                   block_q=16, block_k=16)

    dots = _kernel_dots(fwd_bwd, q, k, v, g) \
        + _kernel_dots(lambda q, k, v: fused_attention(
            q, k, v, causal=True, block_q=16, block_k=16), q, k, v)
    # 2 forward (twice: with and without lse), 3 dQ, 4 dK/dV; each of the
    # causal kernels traces its cell twice, masked and not
    assert len(dots) == 2 * (2 + 3 + 4 + 2)
    for eqn in dots:
        assert [str(x.aval.dtype) for x in eqn.invars] == [dtype, dtype]
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert eqn.outvars[0].aval.dtype == jnp.float32


def test_flash_dead_block_copies_change_nothing(monkeypatch, flash_sub):
    """The index maps point a dead causal cell at a block that is in VMEM
    anyway; with every cell naming its own block instead (the copies
    made), outputs and gradients are the same bit for bit."""
    from mxnet_tpu.ops import pallas_kernels as pk
    flash_sub(8)
    rs = np.random.RandomState(11)
    q, k, v, g = (jnp.asarray(rs.normal(0, 1, (1, 64, 2, 16))
                              .astype(np.float32)) for _ in range(4))

    def run():
        out, lse = fused_attention_fwd(q, k, v, causal=True, block_q=16,
                                       block_k=16)
        return (out, lse) + fused_attention_bwd(
            q, k, v, out, lse, g, causal=True, block_q=16, block_k=16)

    skipped = run()
    renamed = []

    def own_block(live, index, held):
        renamed.append(1)
        return index

    monkeypatch.setattr(pk, "_skip_dead_copy", own_block)
    copied = run()
    assert renamed, "the index maps no longer go through _skip_dead_copy"
    for a, b in zip(skipped, copied):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("window", [0, 8, 24, 5])
@pytest.mark.parametrize("block_q,block_k,sub_k",
                         [(16, 16, 8), (8, 32, 8), (32, 8, 8),
                          (16, 64, 16), (64, 64, 32)])
def test_live_sub_tiles_counts_match_the_mask(block_q, block_k, sub_k,
                                              window):
    """``_live_sub_tiles`` against the mask itself (causal, and inside the
    window where there is one): the sub-tiles it calls unmasked hold a live
    score everywhere, every sub-tile that holds a live score lies in
    ``[first, n_live)``, and with no window the unmasked and the live ones
    are the prefixes they were."""
    from mxnet_tpu.ops.pallas_kernels import _live_sub_tiles
    T = 64
    gap = np.arange(T)[:, None] - np.arange(T)[None, :]    # [query, key]
    live = (gap >= 0) & ((gap < window) if window else True)
    for qi in range(T // block_q):
        for ki in range(T // block_k):
            with jax.enable_x64(False):     # as the kernels trace it
                got = _live_sub_tiles(jnp.int32(qi), jnp.int32(ki),
                                      causal=True, window=window,
                                      block_q=block_q, block_k=block_k,
                                      sub_k=sub_k)
            first, full_from, full_to, n_live = (int(g) for g in got)
            rows = live[qi * block_q:(qi + 1) * block_q]
            tiles = [rows[:, ki * block_k + t * sub_k:
                          ki * block_k + (t + 1) * sub_k]
                     for t in range(block_k // sub_k)]
            assert 0 <= first <= full_from <= full_to <= n_live <= len(tiles)
            assert all(t.all() for t in tiles[full_from:full_to])
            assert not any(t.any() for t in tiles[:first] + tiles[n_live:])
            # no more is walked, and no more is masked, than has to be
            assert all(t.any() for t in tiles[first:n_live])
            assert not any(t.all() for t in tiles[first:full_from]
                           + tiles[full_to:n_live]) or full_from == full_to
            if not window:
                assert (first, full_from) == (0, 0)
                assert full_to == sum(t.all() for t in tiles)
                assert n_live == sum(t.any() for t in tiles)
    assert _live_sub_tiles(0, 0, causal=False, window=0, block_q=block_q,
                           block_k=block_k, sub_k=sub_k) \
        == (0, 0) + (block_k // sub_k,) * 2


def test_flash_fwd_lse_is_row_logsumexp():
    """The residual really is logsumexp of the scaled (masked) logits —
    the invariant the backward rebuilds p from."""
    rs = np.random.RandomState(8)
    B, T, H, D = 1, 32, 1, 8
    q = jnp.asarray(rs.normal(0, 1, (B, T, H, D)).astype(np.float32))
    scale = float(1.0 / np.sqrt(D))
    _, lse = fused_attention_fwd(q, q, q, causal=True, block_q=16,
                                 block_k=8)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(q)) * scale
    s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
    want = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) \
        + s.max(-1)                                   # (B,H,T)
    # lane-major: one float32 a query, (B*H, 1, T)
    assert lse.shape == (B * H, 1, T) and lse.dtype == jnp.float32
    got = np.asarray(lse).reshape(B, H, T)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_flash_bwd_bf16_tolerance():
    rs = np.random.RandomState(9)
    B, T, H, D = 1, 32, 2, 16
    mk = lambda: jnp.asarray(
        rs.normal(0, 1, (B, T, H, D)).astype(np.float32)).astype(
        jnp.bfloat16)
    q, k, v, g = mk(), mk(), mk(), mk()
    out, lse = fused_attention_fwd(q, k, v, causal=True, block_q=16,
                                   block_k=16)
    dq, dk, dv = fused_attention_bwd(q, k, v, out, lse, g, causal=True,
                                     block_q=16, block_k=16)
    scale = float(1.0 / np.sqrt(D))
    f32 = lambda x: jnp.asarray(np.asarray(x, np.float32))
    _, vjp = jax.vjp(
        lambda a, b, c: _naive_attention(a, b, c, causal=True,
                                         scale=scale),
        f32(q), f32(k), f32(v))
    for got, want in zip((dq, dk, dv), vjp(f32(g))):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=0.1, atol=0.05)
