"""Pallas TPU kernels for the hot paths XLA can't fuse optimally
(SURVEY.md §7 build plan reserves Pallas for exactly these).

Kernels:
  * two_bit_compress — fused error-feedback gradient quantization
    (reference src/kvstore/gradient_compression.cc quantize_2bit): ONE
    VMEM pass reads grad + residual and writes the {-t, 0, +t} quantized
    gradient plus the new residual.  XLA would emit this as two
    elementwise passes over HBM; fusing halves the bandwidth of the
    kvstore compression hop.
  * fused_attention — single-chip attention with the (Tq, Tk) score block
    kept entirely in VMEM: per q-block, scores/softmax/weighted-sum happen
    on-chip and HBM never holds the (T, T) matrix.  This is the kernel
    form of parallel/ring.py's `_block_attn`; ring attention composes it
    across chips.

Operands that live on a TPU get the Mosaic lowering; anywhere else the
kernels run through the Pallas interpreter (pallas_call(interpret=True)),
which is how the CPU tests reach the same code.  Every pallas_call carries
a stable ``name=`` so a compiled program can be checked for it
(chip_smoke.py) and a profile can find it.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["two_bit_compress", "fused_attention", "fused_attention_fwd",
           "fused_attention_bwd", "decode_attention",
           "decode_attention_pool", "kv_write", "kv_pack", "quantize_weight",
           "chunk_attention", "chunk_attn_rows",
           "quant_matmul", "grouped_matmul", "mla_attention", "latent_write",
           "mla_chunk_rows", "latent_row_lanes"]


def _interpret(*arrays) -> bool:
    """Interpreter mode off-TPU — real lowering on TPU.  Decided by where
    the INPUTS live, not the default backend: kvstore/host arrays sit on
    the CPU device even when a TPU is attached."""
    for a in arrays:
        if isinstance(a, jax.Array):
            try:
                return not all(d.platform == "tpu" for d in a.devices())
            except Exception:
                break
    return jax.default_backend() != "tpu"


def _out_struct(shape, dtype, *operands):
    """``out_shape`` entry for a pallas_call that may sit inside a
    ``shard_map``: the output varies over the same manual mesh axes as the
    operands, and ``check_vma`` wants that stated."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ---------------------------------------------------------------------------
# two-bit quantization with error feedback
# ---------------------------------------------------------------------------

_LANES = 1024          # flattened row width: 8 sublanes x 128 lanes


def _two_bit_kernel(g_ref, r_ref, q_ref, nr_ref, *, t):
    comp = g_ref[:] + r_ref[:]
    # exact f32 scalars: a weak python float would promote to f64 under
    # jax_enable_x64 and the Mosaic/interpret lowering rejects f64 here
    t32 = jnp.float32(t)
    q = jnp.where(comp >= t32, t32,
                  jnp.where(comp <= -t32, -t32, jnp.float32(0.0)))
    q_ref[:] = q.astype(g_ref.dtype)
    nr_ref[:] = (comp - q).astype(g_ref.dtype)


def two_bit_compress(grad: jax.Array, residual: jax.Array,
                     threshold: float = 0.5, use_pallas=None):
    """Fused quantize + residual update.  Any shape/dtype; returns
    (quantized, new_residual) with grad's shape.

    Default path is the plain-XLA formulation: measured on chip
    (tools/bench_pallas.py, 25.6M elements) XLA fuses the whole
    quantize+feedback chain into ONE elementwise pass at 2.7 ms vs the
    Pallas kernel's 3.9 ms — the compiler wins on pure elementwise
    streaming, so the kernel stays only as an opt-in
    (MXNET_TPU_PALLAS_COMPRESS=1) and a Pallas reference."""
    if use_pallas is None:
        use_pallas = os.environ.get("MXNET_TPU_PALLAS_COMPRESS", "0") == "1"
    if not use_pallas:
        return _two_bit_xla(grad, residual, float(threshold))
    return _two_bit_jit(grad, residual, threshold,
                        _interpret(grad, residual))


@functools.partial(jax.jit, static_argnames=("t",))
def _two_bit_xla(grad, residual, t):
    comp = grad.astype(jnp.float32) + residual.astype(jnp.float32)
    q = jnp.where(comp >= t, t, jnp.where(comp <= -t, -t, 0.0))
    return q.astype(grad.dtype), (comp - q).astype(grad.dtype)


_BLOCK_ROWS = 256    # 4 VMEM buffers x (256, 128) f32 = 512 KB live


@functools.partial(jax.jit, static_argnames=("threshold", "interpret"))
def _two_bit_jit(grad, residual, threshold, interpret):
    shape, dtype = grad.shape, grad.dtype
    n = grad.size
    rows = -(-n // _LANES)
    # grid over row blocks: gradients are arbitrarily large (a ResNet-50
    # push is 25M elements = 100 MB f32), so the kernel must stream —
    # one whole-array block would blow the ~16 MB VMEM budget
    rows = -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS
    pad = rows * _LANES - n
    g2 = jnp.pad(grad.reshape(-1).astype(jnp.float32), (0, pad)) \
        .reshape(rows, _LANES)
    r2 = jnp.pad(residual.reshape(-1).astype(jnp.float32), (0, pad)) \
        .reshape(rows, _LANES)
    kern = functools.partial(_two_bit_kernel, t=float(threshold))
    with jax.enable_x64(False):   # Mosaic cannot take i64 grid indices
        q2, nr2 = pl.pallas_call(
            kern,
            grid=(rows // _BLOCK_ROWS,),
            in_specs=[
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
            ],
            out_specs=(
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
            ),
            out_shape=(_out_struct((rows, _LANES), jnp.float32, g2, r2),
                       _out_struct((rows, _LANES), jnp.float32, g2, r2)),
            interpret=interpret, name="two_bit_compress",
        )(g2, r2)
    q = q2.reshape(-1)[:n].reshape(shape).astype(dtype)
    nr = nr2.reshape(-1)[:n].reshape(shape).astype(dtype)
    return q, nr


# ---------------------------------------------------------------------------
# fused attention
# ---------------------------------------------------------------------------

_NEG_BIG = -1e30      # -inf would make exp(m_prev - m_new) NaN on init

# All three flash kernels hold a score tile TRANSPOSED: keys on sublanes,
# queries on lanes, ``K·Qᵀ``.  What follows from that, on a chip whose
# vector unit works on (8 sublanes, 128 lanes) registers and reduces
# across lanes only through the slow cross-lane unit:
#   * the softmax's row maximum and row sum run down the sublanes, i.e.
#     are elementwise across registers but for one last step a 128 queries;
#   * the row statistics (running max and sum, logsumexp, delta) are (1, bq)
#     rows that broadcast down the sublanes as they are, and travel through
#     HBM lane-major: (B*H, 1, T) float32, 4 bytes a query, where the
#     lane-broadcast (B*H, T, 128) form the kernels used until PR 27 took
#     512 and ``flash_bwd_dkv`` read it once per k block;
#   * what accumulates per query, the forward's output and dQ, is a
#     (D, bq) tile with every lane in use at D = 64, fed by ``Vᵀ·Pᵀ`` and
#     ``Kᵀ·dSᵀ``; the wrappers hand V and K over transposed, (B*H, D, T),
#     and take the result back the same way, inside the transposes that
#     the (B, T, H, D) layout costs them anyway;
#   * every product contracts the way the MXU takes it: no tile is
#     transposed inside a cell.
# The products take their operands in the dtype they arrived in (bfloat16 x
# bfloat16 for a bfloat16 model, float32 products for float32 inputs) and
# accumulate in float32; ``p`` and ``ds`` are rounded to that dtype only as
# operands.  Scores, statistics, ``exp`` and accumulators are float32.

# The key rows of one inner step: a grid cell holds a (block_q, D) and a
# (block_k, D) block and walks the key block in sub-tiles of this many
# rows.  The blocks the grid copies can then be large (a grid step costs
# a fixed 0.15-0.35 us) while dead sub-tiles are skipped and only those
# on the diagonal are masked at the finer grain.  Fitted to the block
# like the blocks to T.  Chip timings: PERF.md section 6, PR 27.
_FLASH_SUB_K = 512

_Q_LANES = 128                     # what lies along the lanes: multiples

_NT = (((1,), (1,)), ((), ()))     # a (m, d) x (n, d) -> (m, n) product
_NN = (((1,), (0,)), ((), ()))     # a (m, n) x (n, d) -> (m, d) product


def _mxu_dot(a, b, dims):
    """Matrix product in the operands' own dtype, accumulated in float32.
    Float32 operands get the package-wide "highest" precision (float32
    products, mxnet_tpu/__init__.py); narrower ones go to the MXU as they
    are, which Mosaic has to be told: it refuses bfloat16 operands under
    a float32 contract precision."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _sublane_tile(dtype):
    """Rows of one register tile: 8 of 32 bits (8 for f32, 16 for bf16)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _fit_block(block, T, tile):
    """Largest halving of ``block`` that divides ``T`` and is a multiple
    of ``tile`` (the dtype's sublane tile for a block of rows, 128 for one
    along the lanes); a length no such block divides is taken whole, which
    Mosaic accepts as "equal to the array dimension"."""
    b = min(block, T)
    while T % b and b > tile:
        b //= 2
    return b if T % b == 0 and b % tile == 0 else T


def _pick_blocks(block_q, block_k, Tq, Tk, D, dtype, kind, interpret):
    """Resolve (block_q, block_k, sub_k): explicit argument wins, then the
    autotune cache (ops/autotune.py), then the static default — and
    either way fit them to the sequence lengths (:func:`_fit_block`), and
    the inner sub-tile to the key block.  Query blocks lie along the
    lanes, and so do the sub-tiles of Kᵀ and Vᵀ: Mosaic wants both in
    multiples of 128 (the interpreter takes any)."""
    if block_q is None or block_k is None:
        from . import autotune as _autotune
        tq, tk = _autotune.flash_blocks(kind, Tq, Tk, D, dtype)
        block_q = block_q or tq
        block_k = block_k or tk
    rows = _sublane_tile(dtype)
    lanes = rows if interpret else _Q_LANES
    bq = _fit_block(block_q, Tq, lanes)
    bk = _fit_block(block_k, Tk, rows)
    return bq, bk, _fit_block(_FLASH_SUB_K, bk, lanes)


def _live_sub_tiles(qi, ki, *, causal, window, block_q, block_k, sub_k):
    """``(first, unmasked_from, unmasked_to, n_live)`` over the cell's
    ``block_k // sub_k`` key sub-tiles, counted from the block's first.
    Sub-tiles before ``first`` lie wholly left of the window's band and
    those from ``n_live`` on wholly above the diagonal: neither is ever
    touched.  ``[unmasked_from, unmasked_to)`` hold a live score for every
    query of the block and take no mask; the sub-tiles round them straddle
    the band's left edge (``[first, unmasked_from)``, a window only) or
    the diagonal (``[unmasked_to, n_live)``).  With no window the first two
    are the constant 0."""
    n = block_k // sub_k
    if not causal:
        return 0, 0, n, n
    first_q = qi * block_q - ki * block_k       # relative to the key block
    n_unmasked = jnp.minimum(
        jax.lax.div(jnp.maximum(first_q + 1, 0), sub_k), n)
    n_live = jnp.minimum(
        jax.lax.div(jnp.maximum(first_q + block_q - 1 + sub_k, 0), sub_k),
        n)
    if not window:
        return 0, 0, n_unmasked, n_live
    # query i sees keys i - window < j <= i: the block's first query sets
    # the leftmost live key, its last the leftmost key all of them see
    first = jnp.minimum(
        jax.lax.div(jnp.maximum(first_q - window + 1, 0), sub_k), n_live)
    full = jax.lax.div(
        jnp.maximum(first_q + block_q - window, 0) + sub_k - 1, sub_k)
    unmasked_from = jnp.clip(full, first, n_live)
    return first, unmasked_from, jnp.maximum(n_unmasked, unmasked_from), \
        n_live


def _walk_sub_tiles(tile, qi, ki, **geometry):
    """Run ``tile(t, masked)`` over the cell's live key sub-tiles: those
    on the band's left edge (a window only), the unmasked ones, then
    those on the diagonal."""
    first, unmasked_from, unmasked_to, n_live = _live_sub_tiles(
        qi, ki, **geometry)

    def run(masked):
        def body(t, carry):
            tile(t, masked)
            return carry
        return body

    if geometry["window"]:
        jax.lax.fori_loop(first, unmasked_from, run(True), None)
    jax.lax.fori_loop(unmasked_from, unmasked_to, run(False), None)
    if geometry["causal"]:
        jax.lax.fori_loop(unmasked_to, n_live, run(True), None)


def _sub_tile_start(t, sub_k, block_k):
    """First key of sub-tile ``t`` within its block: static where the
    block is one sub-tile (a length that is no multiple of 128 is taken
    whole, and Mosaic slices along lanes only at provable multiples)."""
    return 0 if sub_k == block_k else pl.multiple_of(t * sub_k, sub_k)


def _scores_t(k, q, scale, diagonal, window=0):
    """The (sub_k, bq) transposed score tile ``K·Qᵀ·scale``; ``diagonal``
    is None, or the tile's first (key, query) position for the causal
    mask (and the window's, where there is one), which only a tile that
    straddles the diagonal or the band's left edge needs."""
    st = _mxu_dot(k, q, _NT) * scale
    if diagonal is not None:
        k_first, q_first = diagonal
        k_idx = k_first + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        q_idx = q_first + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
        seen = q_idx >= k_idx
        if window:
            seen = seen & (q_idx - k_idx < window)
        st = jnp.where(seen, st, jnp.float32(_NEG_BIG))
    return st


def _skip_dead_copy(live, index, held):
    """Block index for a grid cell's copy: its own where the cell is
    live, and where it is dead the one that is (or is about to be) in
    VMEM anyway, so that the pipeline issues no copy for a block nobody
    reads."""
    return jnp.where(live, index, held)


def _first_live_k_block(i, window, block_q, block_k):
    """First key block that query block ``i`` sees: 0 without a window."""
    if not window:
        return 0
    return jax.lax.div(jnp.maximum(i * block_q - window + 1, 0), block_k)


def _live_k_block(causal, window, block_q, block_k):
    """Key block a cell of the grids whose inner axis runs over key blocks
    (``flash_fwd``, ``flash_bwd_dq``) copies.  A block wholly above the
    diagonal names the first block the NEXT row of cells reads (block 0
    without a window): the copy starts under the row's last live cell.  A
    block wholly left of the window's band names the row's first live one."""
    def block(i, j):
        if causal:
            first = functools.partial(_first_live_k_block, window=window,
                                      block_q=block_q, block_k=block_k)
            if window:
                j = jnp.maximum(j, first(i))
            j = _skip_dead_copy(j * block_k <= i * block_q + block_q - 1,
                                j, first(i + 1))
        return j
    return block


def _kv_head(b, rep):
    """Flat (batch, key/value head) index that flat (batch, query head)
    ``b`` reads: ``rep`` query heads share one."""
    return b if rep == 1 else jax.lax.div(b, rep)


def _flash_kernel(q_ref, k_ref, vt_ref, ot_ref, *rest, scale, causal,
                  window, block_q, block_k, sub_k, nk, with_lse):
    """Flash attention cell: one (block_q, D) query block against one
    (block_k, D) K block and (D, block_k) Vᵀ block, walked in sub_k keys,
    with the running (max, sum, acc) online-softmax state in VMEM
    scratch.  The k-axis is the innermost grid dimension, which TPU
    executes sequentially — the scratch carries across k steps and the
    (D, block_q) output is finalized on the last one."""
    if with_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        lse_ref = None
        acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, jnp.float32(_NEG_BIG))
        l_ref[:] = jnp.zeros_like(l_ref)

    def _tile(t, masked):
        start = _sub_tile_start(t, sub_k, block_k)
        keys = pl.ds(start, sub_k)
        st = _scores_t(k_ref[keys, :], q_ref[:], scale,
                       (ki * block_k + start, qi * block_q)
                       if masked else None, window)     # (sub_k, bq)
        m_prev = m_ref[:]                               # (1, bq)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        pt = jnp.exp(st - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(pt, axis=0, keepdims=True)
        m_ref[:] = m_new
        vt = vt_ref[:, keys]                            # (D, sub_k)
        acc_ref[:] = acc_ref[:] * corr + _mxu_dot(vt, pt.astype(vt.dtype),
                                                  _NN)

    # causal: sub-tiles wholly above this q block's last row are skipped,
    # and with a window those wholly left of its first row's band
    _walk_sub_tiles(_tile, qi, ki, causal=causal, window=window,
                    block_q=block_q, block_k=block_k, sub_k=sub_k)

    @pl.when(ki == nk - 1)
    def _finish():
        ot_ref[:] = (acc_ref[:] / l_ref[:]).astype(ot_ref.dtype)
        if lse_ref is not None:
            # logsumexp of the SCALED logits: the backward's whole
            # softmax state, one float32 a query
            lse_ref[:] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:],
                                                        jnp.float32(1e-37)))


# batch·head and the outer block axis are independent; the inner axis
# carries the scratch
_GRID_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _flash_call(operands, *, with_lse, interpret, **geometry):
    """``flash_fwd`` over (B*H, T, D) q, (B*G, T, D) k and (B*G, D, T) vᵀ,
    ``rep`` = H // G query heads to a key/value head: the output
    transposed, (B*H, D, Tq), and the (B*H, 1, Tq) logsumexp or None."""
    qf, kf, _ = operands
    BH, Tq, D = qf.shape
    bq, bk = geometry["block_q"], geometry["block_k"]
    nk = kf.shape[1] // bk
    rep = BH // kf.shape[0]
    live_k = _live_k_block(geometry["causal"], geometry["window"], bq, bk)
    out_shape = [_out_struct((BH, D, Tq), qf.dtype, *operands)]
    out_specs = [pl.BlockSpec((None, D, bq), lambda b, i, j: (b, 0, i))]
    if with_lse:
        out_shape.append(_out_struct((BH, 1, Tq), jnp.float32, *operands))
        out_specs.append(
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)))
    # this package runs with jax_enable_x64 on (mxnet int64 parity); grid
    # index maps would then trace their literals as i64, which Mosaic
    # cannot legalize — trace the kernel in an x64-off scope
    with jax.enable_x64(False):
        res = pl.pallas_call(
            functools.partial(_flash_kernel, nk=nk, with_lse=with_lse,
                              **geometry),
            grid=(BH, Tq // bq, nk),
            in_specs=[
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (
                    _kv_head(b, rep), live_k(i, j), 0)),
                pl.BlockSpec((None, D, bk), lambda b, i, j: (
                    _kv_head(b, rep), 0, live_k(i, j))),
            ],
            out_specs=tuple(out_specs) if with_lse else out_specs[0],
            out_shape=tuple(out_shape) if with_lse else out_shape[0],
            scratch_shapes=[
                pltpu.VMEM((D, bq), jnp.float32),     # acc
                pltpu.VMEM((1, bq), jnp.float32),     # running max
                pltpu.VMEM((1, bq), jnp.float32),     # running sum
            ],
            compiler_params=_GRID_PARAMS,
            interpret=interpret, name="flash_fwd",
        )(*operands)
    return res if with_lse else (res, None)


def _heads_major(x):
    """(B, T, H, D) -> (B*H, T, D)."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _heads_major_t(x):
    """(B, T, H, D) -> (B*H, D, T): positions along the lanes."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 3, 1).reshape(B * H, D, T)


def _from_heads_major_t(xt, B):
    """(B*H, D, T) -> (B, T, H, D)."""
    BH, D, T = xt.shape
    return xt.reshape(B, BH // B, D, T).transpose(0, 3, 1, 2)


def _check_heads_and_window(q, k, v, causal, window):
    if k.shape != v.shape or q.shape[2] % k.shape[2]:
        raise ValueError("fused_attention: key and value of one shape whose "
                         "head count divides the query's, got q %s k %s v %s"
                         % (q.shape, k.shape, v.shape))
    if window and (window < 0 or not causal):
        raise ValueError("fused_attention: a window (%d) is a band below "
                         "the diagonal, so it needs causal=True" % window)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, with_lse, window):
    _check_heads_and_window(q, k, v, causal, window)
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    interpret = _interpret(q, k, v)
    bq, bk, sub_k = _pick_blocks(block_q, block_k, q.shape[1], k.shape[1],
                                 D, q.dtype, "fwd", interpret)
    out_t, lse = _flash_call(
        (_heads_major(q), _heads_major(k), _heads_major_t(v)), scale=scale,
        causal=causal, window=int(window), block_q=bq, block_k=bk,
        sub_k=sub_k, with_lse=with_lse, interpret=interpret)
    return _from_heads_major_t(out_t, q.shape[0]), lse


def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale=None,
                    block_q=None, block_k=None, window: int = 0) -> jax.Array:
    """Flash attention forward: K/V-blocked online softmax.

    q: (B, T, H, D) (the parallel/ring.py layout); k/v: (B, T, G, D) with
    G dividing H, query head n reading key/value head n // (H // G).
    ``window`` (0 = none; needs ``causal``): query i sees keys
    i - window < j <= i, and key blocks wholly left of that band are
    skipped like those above the diagonal.  Returns (B, T, H, D).
    Per grid cell only (block_q + 2*block_k, D) tiles and
    a (sub_k, block_q) score tile live in VMEM — HBM traffic is
    O(T*D) and the sequence length is bounded by HBM, not VMEM (the
    round-3 kernel held ALL of K/V in VMEM and topped out near T=8k;
    this one runs T=32k+ single-chip, tools/bench_pallas.py).  The matrix
    products run in the dtype of q/k/v with float32 accumulation.

    ``block_q``/``block_k`` default to the autotune cache
    (ops/autotune.py; MXNET_TPU_AUTOTUNE knobs), then to
    ``autotune.DEFAULT_FLASH_BLOCKS["fwd"]`` (from v5e timings), fitted
    to T; on the chip a query block is a multiple of 128 or all of T.
    """
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, False,
                      window)[0]


def fused_attention_fwd(q, k, v, causal=False, scale=None,
                        block_q=None, block_k=None, window=0):
    """Forward for the custom vjp: returns ``(out, lse)`` where ``lse``
    is the per-row logsumexp of the scaled logits, shape
    ``(B*H, 1, Tq)`` f32 (lane-major, 4 bytes a query).  With this
    residual the backward never rematerializes the softmax normalizer:
    one extra O(T) output instead of re-running the online softmax."""
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, True,
                      window)


def _p_and_ds_t(start, q_ref, do_ref, k_ref, v_ref, lse_ref, dl_ref, *,
                scale, diagonal, sub_k, window):
    """What both backward kernels rebuild of the sub-tile whose first key
    is ``start``, transposed, (sub_k, bq): ``p`` from the saved row logsumexp (the scores are
    recomputed, one exp each; the online softmax is not), and
    ``ds = p·(dp - delta)·scale`` with ``delta = rowsum(dO·O)`` folding
    the normalizer's term."""
    keys = pl.ds(start, sub_k)
    st = _scores_t(k_ref[keys, :], q_ref[:], scale, diagonal, window)
    pt = jnp.exp(st - lse_ref[:])               # masked scores -> 0
    dpt = _mxu_dot(v_ref[keys, :], do_ref[:], _NT)
    return keys, pt, pt * (dpt - dl_ref[:]) * scale


def _flash_bwd_dq_kernel(q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref,
                         dl_ref, dqt_ref, acc_ref, *, scale, causal,
                         window, block_q, block_k, sub_k, nk):
    """dQ cell: one (bq, D) query block against the sequential k-axis,
    ``dQᵀ += Kᵀ·dSᵀ`` into a (D, bq) accumulator."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile(t, masked):
        start = _sub_tile_start(t, sub_k, block_k)
        keys, _, dst = _p_and_ds_t(
            start, q_ref, do_ref, k_ref, v_ref, lse_ref, dl_ref,
            scale=scale, sub_k=sub_k, window=window,
            diagonal=(ki * block_k + start, qi * block_q) if masked
            else None)
        kt = kt_ref[:, keys]                        # (D, sub_k)
        acc_ref[:] = acc_ref[:] + _mxu_dot(kt, dst.astype(kt.dtype), _NN)

    _walk_sub_tiles(_tile, qi, ki, causal=causal, window=window,
                    block_q=block_q, block_k=block_k, sub_k=sub_k)

    @pl.when(ki == nk - 1)
    def _finish():
        dqt_ref[:] = acc_ref[:].astype(dqt_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                          causal, window, block_q, block_k, sub_k, nq, rep):
    """dK/dV cell: one (bk, D) key/value block against the sequential
    inner axis, ``dV += Pᵀ·dO`` and ``dK += dSᵀ·Q`` into VMEM scratch.
    The inner axis runs over the q blocks of each of the ``rep`` query
    heads that read this key/value head, one head after the other, so the
    group's sum forms in the scratch."""
    ki = pl.program_id(1)
    step = pl.program_id(2)
    qi = step if rep == 1 else jax.lax.rem(step, nq)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _tile(t, masked):
        start = _sub_tile_start(t, sub_k, block_k)
        keys, pt, dst = _p_and_ds_t(
            start, q_ref, do_ref, k_ref, v_ref, lse_ref, dl_ref,
            scale=scale, sub_k=sub_k, window=window,
            diagonal=(ki * block_k + start, qi * block_q) if masked
            else None)
        do = do_ref[:]
        q = q_ref[:]
        dv_acc[keys, :] = dv_acc[keys, :] + _mxu_dot(pt.astype(do.dtype),
                                                     do, _NN)
        dk_acc[keys, :] = dk_acc[keys, :] + _mxu_dot(dst.astype(q.dtype),
                                                     q, _NN)

    # causal: q blocks entirely ABOVE this k block see none of it
    _walk_sub_tiles(_tile, qi, ki, causal=causal, window=window,
                    block_q=block_q, block_k=block_k, sub_k=sub_k)

    @pl.when(step == rep * nq - 1)
    def _finish():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_dq_call(operands, *, interpret, **geometry):
    """dQᵀ, (B*H, D, Tq): grid (B*H, q blocks, k blocks), k innermost.
    ``operands``: q, k, kᵀ, v, do, lse, delta; k, kᵀ and v by key/value
    head."""
    qf, kf = operands[:2]
    BH, Tq, D = qf.shape
    bq, bk = geometry["block_q"], geometry["block_k"]
    nk = kf.shape[1] // bk
    rep = BH // kf.shape[0]
    live_k = _live_k_block(geometry["causal"], geometry["window"], bq, bk)
    q_spec = pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((None, bk, D), lambda b, i, j: (
        _kv_head(b, rep), live_k(i, j), 0))
    kt_spec = pl.BlockSpec((None, D, bk), lambda b, i, j: (
        _kv_head(b, rep), 0, live_k(i, j)))
    row_spec = pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i))
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_flash_bwd_dq_kernel, nk=nk, **geometry),
            grid=(BH, Tq // bq, nk),
            in_specs=[q_spec, k_spec, kt_spec, k_spec, q_spec, row_spec,
                      row_spec],
            out_specs=pl.BlockSpec((None, D, bq), lambda b, i, j: (b, 0, i)),
            out_shape=_out_struct((BH, D, Tq), qf.dtype, *operands),
            scratch_shapes=[pltpu.VMEM((D, bq), jnp.float32)],
            compiler_params=_GRID_PARAMS,
            interpret=interpret, name="flash_bwd_dq",
        )(*operands)


def _flash_dkv_call(operands, *, interpret, **geometry):
    """dK, dV, (B*G, Tk, D): grid (B*G, k blocks, rep x q blocks), the q
    blocks of a group's ``rep`` query heads innermost.
    ``operands``: q, k, v, do, lse, delta."""
    qf, kf, vf = operands[:3]
    BH, Tq, D = qf.shape
    BG, Tk = kf.shape[:2]
    bq, bk = geometry["block_q"], geometry["block_k"]
    window = geometry["window"]
    nq = Tq // bq
    rep = BH // BG

    def live_q(j, i):
        # the q blocks above k block j see none of it and come first in
        # its row of cells; they name the first live one.  With a window
        # those past the band come last and name the last live one
        if geometry["causal"]:
            if window:
                i = jnp.minimum(i, jax.lax.div(j * bk + bk + window - 2, bq))
            i = _skip_dead_copy(i * bq + bq - 1 >= j * bk, i,
                                jnp.minimum(jax.lax.div(j * bk, bq), nq - 1))
        return i

    def q_at(b, j, t):
        """(flat query head, q block) of inner step ``t``."""
        if rep == 1:
            return b, live_q(j, t)
        return b * rep + jax.lax.div(t, nq), live_q(j, jax.lax.rem(t, nq))

    def q_index(b, j, t):
        head, i = q_at(b, j, t)
        return head, i, 0

    def row_index(b, j, t):
        head, i = q_at(b, j, t)
        return head, 0, i

    q_spec = pl.BlockSpec((None, bq, D), q_index)
    row_spec = pl.BlockSpec((None, 1, bq), row_index)
    kv_spec = pl.BlockSpec((None, bk, D), lambda b, j, t: (b, j, 0))
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_flash_bwd_dkv_kernel, nq=nq, rep=rep,
                              **geometry),
            grid=(BG, Tk // bk, rep * nq),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=(kv_spec, kv_spec),
            out_shape=(_out_struct((BG, Tk, D), kf.dtype, *operands),
                       _out_struct((BG, Tk, D), vf.dtype, *operands)),
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            compiler_params=_GRID_PARAMS,
            interpret=interpret, name="flash_bwd_dkv",
        )(*operands)


def fused_attention_bwd(q, k, v, out, lse, do, causal=False, scale=None,
                        block_q=None, block_k=None, window=0):
    """Flash attention backward: K/V-blocked dQ/dK/dV from the saved
    logsumexp residual — the online softmax is not re-run (each kernel
    recomputes its score tiles, one exp a score), and no (T, T) tensor
    reaches HBM (the einsum-vjp fallback materializes the full
    probability matrix AND its gradient: ~2·B·H·T² values of HBM traffic
    per layer that these kernels never touch).

    q/out/do: (B, T, H, D), k/v: (B, T, G, D) as in
    :func:`fused_attention`; ``lse``: (B*H, 1, Tq) f32 from
    :func:`fused_attention_fwd`.  Returns (dq, dk, dv) in the input
    dtypes and shapes: dK and dV are summed over a group's query heads.
    Two pallas calls: dQ accumulates over the sequential
    k-axis, dK/dV over the sequential q-axis.  Block sizes default to
    the autotune cache ("bwd" entry), then to
    ``autotune.DEFAULT_FLASH_BLOCKS["bwd"]``, fitted to T."""
    _check_heads_and_window(q, k, v, causal, window)
    B, Tq, H, D = q.shape
    Tk, G = k.shape[1:3]
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    interpret = _interpret(q, k, v)
    bq, bk, sub_k = _pick_blocks(block_q, block_k, Tq, Tk, D, q.dtype,
                                 "bwd", interpret)
    qf, kf, vf, dof = (_heads_major(x) for x in (q, k, v, do))
    # delta = rowsum(dO · O): one cheap fused O(T·D) pass in XLA, lane-
    # major like lse so both ride the same (1, bq) blocks
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1).reshape(B * H, 1, Tq)
    geometry = dict(scale=scale, causal=causal, window=int(window),
                    block_q=bq, block_k=bk, sub_k=sub_k, interpret=interpret)
    dq_t = _flash_dq_call((qf, kf, _heads_major_t(k), vf, dof, lse, delta),
                          **geometry)
    dk, dv = _flash_dkv_call((qf, kf, vf, dof, lse, delta), **geometry)

    def unflat(x):
        return x.reshape(B, G, Tk, D).transpose(0, 2, 1, 3)

    return _from_heads_major_t(dq_t, B), unflat(dk), unflat(dv)


# ---------------------------------------------------------------------------
# grouped matrix product (the held experts of parallel/moe.py)
# ---------------------------------------------------------------------------
#
# ``x`` (m, k) holds the rows of ``g`` groups one after the other,
# ``sizes[i]`` rows for group i, and each group has its own (k, n) matrix.
# On the TPU this is jax's megablox kernel pair (``gmm`` for the product
# and for the rows' gradient, ``tgmm`` for the matrices'): its grid runs
# over the row tiles the groups really own (a dynamic bound), so time
# follows ``sum(sizes)`` and not m.  A forward product bound by reading
# its weights takes a kernel of this module's own on megablox's grid,
# :func:`_gmm_group_ahead`, that copies the next group's weights while
# every visit of the current group runs.  ``jax.lax.ragged_dot`` lowers to a
# kernel of XLA's own that is as fast (17 ms a step of
# trinity-mini.train-b1x8k against a least time of 6.3; PERF.md section 6,
# PR 33) but drops the ``jax.named_scope`` path of the call, so no trace
# reader can tell whose time it is; a Pallas call keeps it.  Anywhere else
# (the CPU tests) ``ragged_dot``'s reference lowering does the work.
#
# Rows past ``sum(sizes)`` belong to no group: no kernel writes them, and
# what the result holds there is whatever the buffer held before.  The
# caller masks them.

_GMM_TILING = (512, 1024, 1024)     # rows, contracted, columns
# rows under which a tile's visit is bound by reading its weight tile and
# not by the MXU (the v5e multiplies ~240 rows by a bf16 tile in the time
# it reads it).  Where a group's mean rows fall under it, the forward
# product takes the whole contracted axis (:func:`_gmm_fwd_tiling`): the
# weight block then stays put across the visits to one group, and each
# group's matrix is read once a column tile, whatever row tiles it spans
_GMM_MIN_ROWS = 256
# such a product's VMEM: its two (k, tn) weight slots (the group being
# visited and the next one), and with them the lhs tile, megablox's float32
# accumulator and the output tile, under the scoped VMEM Mosaic allows a
# kernel by default on the v5e (neither kernel asks for more)
_GMM_WEIGHT_VMEM = 8 << 20
_GMM_SCOPED_VMEM = 16 << 20
# and its row tile: a visit pushes its group's whole weight tile through the
# MXU whatever its rows, so fewer rows save nothing (64 read 5% slower than
# 128 on the v5e at 64 rows a group, 256 0.5% slower; PERF.md section 5)
_GMM_RESIDENT_ROWS = 128

# what each grouped product traced in this process took on the TPU
_GMM_TILINGS = {}


def _gmm_tiling(m, groups):
    """The tiling of ``m`` sorted rows over ``groups`` groups.  A row tile is
    the MXU's whole work for every group that has a row in it, so a tile of
    eight times the groups' mean rows or more is halved (down to
    ``_GMM_MIN_ROWS``): 2,048 rows over 32 experts in 512-row tiles made
    the MXU compute eight times the products it kept, more time than
    reading the experts' weights."""
    tm, tk, tn = _GMM_TILING
    mean = -(-m // groups)
    while tm > _GMM_MIN_ROWS and tm >= 8 * mean:
        tm //= 2
    while m % tm:
        tm //= 2
    return tm, tk, tn


def _gmm_vmem(tm, k, tn, itemsize, out_itemsize):
    """VMEM bytes of a (tm, k, tn) tile of megablox's forward product:
    lhs and weight tiles and the output tile double-buffered, the float32
    accumulator once.  :func:`_gmm_group_ahead` holds the same but the
    accumulator."""
    return (2 * (tm * k + k * tn) * itemsize + tm * tn * 4
            + 2 * tm * tn * out_itemsize)


def _gmm_fwd_tiling(m, groups, k, n, dtype, out_dtype):
    """The forward product's tiling of ``m`` sorted rows over ``groups``
    groups of (k, n) matrices.  Where the groups' mean rows are under
    ``_GMM_MIN_ROWS`` the product is bound by reading its weights, and it
    takes the whole contracted axis, ``_GMM_RESIDENT_ROWS`` rows and the
    widest multiple of 128 columns that divides ``n`` inside the VMEM
    budgets; anywhere else, or where no such column tile fits, it takes
    :func:`_gmm_tiling`'s.  Returns (tiling, weight_resident)."""
    if -(-m // groups) < _GMM_MIN_ROWS:
        itemsize = jnp.dtype(dtype).itemsize
        out_itemsize = jnp.dtype(out_dtype).itemsize
        tm = _GMM_RESIDENT_ROWS
        while m % tm:
            tm //= 2
        for tn in range(n - n % 128, 0, -128):
            if (n % tn == 0 and 2 * k * tn * itemsize <= _GMM_WEIGHT_VMEM
                    and _gmm_vmem(tm, k, tn, itemsize, out_itemsize)
                    <= _GMM_SCOPED_VMEM):
                return (tm, k, tn), True
    return _gmm_tiling(m, groups), False


def grouped_matmul_tilings():
    """Every grouped product traced in this process for the TPU, in the
    order first traced: dicts of ``pass`` (``forward`` or ``backward``),
    ``m``, ``groups``, ``k``, ``n``, ``dtype``, ``tiling`` (rows,
    contracted, columns), ``weight_resident`` (the whole contracted axis
    in one tile, :func:`_gmm_fwd_tiling`) and ``prefetch``, the pipeline
    that brings the weights: ``group`` (:func:`_gmm_group_ahead`, the next
    group's weights a whole group ahead) or ``megablox`` (one grid step
    ahead).  Filled at trace time; the CPU's ``ragged_dot`` path takes no
    tiling and records nothing."""
    return [dict(v) for v in _GMM_TILINGS.values()]


def _note_tiling(pass_, x, w, tiling, resident):
    m, k = x.shape
    groups, n = w.shape[0], w.shape[-1]
    key = (pass_, m, groups, k, n, str(x.dtype))
    _GMM_TILINGS.setdefault(key, {
        "pass": pass_, "m": m, "groups": groups, "k": k, "n": n,
        "dtype": str(x.dtype), "tiling": tuple(tiling),
        "weight_resident": resident,
        "prefetch": "group" if resident else "megablox"})


def _megablox():
    # the package's ``gmm`` attribute is its custom_vjp function, which
    # hides the module of the same name that holds gmm and tgmm
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _mxu_operands(dtype):
    """Context in which a kernel that names no precision of its own is
    traced: operands narrower than float32 go to the MXU as they are (the
    package-wide "highest" would make Mosaic refuse them, see
    :func:`_mxu_dot`)."""
    if dtype == jnp.float32:
        return contextlib.nullcontext()
    return jax.default_matmul_precision("default")


def _gmm_group_ahead_kernel(offsets, group_ids, m_tile_ids, following,
                            x_ref, w_hbm, o_ref, w_buf, sems, slot_ref, *,
                            dtype):
    """One visit of megablox's grid: row tile ``m_tile_ids[t]`` of group
    ``group_ids[t]`` against column tile ``n``, the whole contracted axis
    in one dot.

    ``w_hbm`` is every group's (k, n) matrix, left in HBM; ``w_buf``
    (2, k, tn) the two slots a group's column tile lands in, ``sems``
    their copies' semaphores, ``slot_ref`` the slot of the group being
    visited.  At a group's first visit the kernel waits for its copy and
    starts the next non-empty group's (``following[g + 1]``; past the last
    group, the first one, ``following[0]``, of the next column tile) into
    the other slot, so that copy runs through all of this group's visits:
    megablox's pipeline would start it one grid step ahead, during this
    group's last visit only.  Rows of the tile outside the group keep what
    the output block holds (megablox's masked store)."""
    n, t = pl.program_id(0), pl.program_id(1)
    tm, tn = o_ref.shape
    groups = w_hbm.shape[0]
    g = group_ids[t]

    def copy(group, col, slot):
        cols = pl.ds(pl.multiple_of(col * tn, tn), tn)
        return pltpu.make_async_copy(w_hbm.at[group, :, cols],
                                     w_buf.at[slot], sems.at[slot])

    @pl.when((n == 0) & (t == 0))
    def _first():
        slot_ref[0] = 1                 # the first group's slot is then 0
        copy(g, n, 0).start()

    @pl.when((t == 0) | (group_ids[jnp.maximum(t - 1, 0)] != g))
    def _new_group():
        slot = 1 - slot_ref[0]
        slot_ref[0] = slot
        copy(g, n, slot).wait()
        after = following[g + 1]
        here = after < groups

        @pl.when(here | (n + 1 < pl.num_programs(0)))
        def _prefetch():
            copy(jnp.where(here, after, following[0]),
                 jnp.where(here, n, n + 1), 1 - slot).start()

    y = jnp.dot(x_ref[...].astype(dtype), w_buf[slot_ref[0]].astype(dtype),
                preferred_element_type=jnp.float32)
    rows = (m_tile_ids[t] * tm
            + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0))
    mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
    o_ref[...] = jnp.where(mine, y, o_ref[...].astype(jnp.float32)
                           ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "tiling", "interpret"))
def _gmm_group_ahead(x, w, sizes, out_dtype, tiling, interpret=False):
    """``megablox.gmm(x, w, sizes, out_dtype, tiling)`` for a tiling whose
    contracted tile is the whole of k (``_gmm_fwd_tiling``'s weight-resident
    one), with the weights copied a group ahead
    (:func:`_gmm_group_ahead_kernel`).  The grid is megablox's, the column
    tiles outer and the visits (row tile, group) of
    ``make_group_metadata`` inner; both axes are sequential, since a copy
    started in one column tile is waited on in the next.  Under
    ``jax.jit`` so that each shape is traced once."""
    m, k = x.shape
    groups, _, n = w.shape
    tm, tk, tn = tiling
    assert tk == k and m % tm == 0 and n % tn == 0, (x.shape, w.shape,
                                                     tiling)
    megablox = _megablox()
    (offsets, group_ids, m_tile_ids), visits = megablox.make_group_metadata(
        group_sizes=sizes, m=m, tm=tm, start_group=0,
        num_nonzero_groups=groups, visit_empty_groups=False)
    # following[j]: the first non-empty group from j on (``groups``: none)
    nonempty = jnp.where(sizes > 0, jnp.arange(groups, dtype=jnp.int32),
                         groups)
    following = jnp.append(jax.lax.cummin(nonempty, reverse=True),
                           jnp.int32(groups))
    dtype = (jnp.bfloat16 if x.dtype == w.dtype == jnp.bfloat16
             else jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, visits),
        in_specs=[pl.BlockSpec((tm, k), lambda c, t, o, g, r, f: (r[t], 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tm, tn), lambda c, t, o, g, r, f: (r[t], c)),
        scratch_shapes=[pltpu.VMEM((2, k, tn), w.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    return pl.pallas_call(
        functools.partial(_gmm_group_ahead_kernel, dtype=dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        # megablox's estimate, so that XLA schedules around the call as
        # it did around megablox's
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(x.size * x.itemsize * (n // tn)
                            + k * n * w.itemsize * group_ids.size
                            + m * n * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret, name="gmm",
    )(offsets, group_ids, m_tile_ids, following, x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_tpu(x, w, sizes, out_dtype):
    tiling, resident = _gmm_fwd_tiling(x.shape[0], w.shape[0], x.shape[1],
                                       w.shape[2], x.dtype, out_dtype)
    _note_tiling("forward", x, w, tiling, resident)
    with _mxu_operands(x.dtype), jax.enable_x64(False):
        if resident:
            return _gmm_group_ahead(x, w, sizes, out_dtype, tiling)
        return _megablox().gmm(x, w, sizes, out_dtype, tiling)


def _gmm_tpu_fwd(x, w, sizes, out_dtype):
    return _gmm_tpu(x, w, sizes, out_dtype), (x, w, sizes)


def _gmm_tpu_bwd(out_dtype, saved, dy):
    megablox = _megablox()
    x, w, sizes = saved
    tiling = _gmm_tiling(x.shape[0], w.shape[0])
    _note_tiling("backward", x, w, tiling, False)
    dy = dy.astype(x.dtype)
    with _mxu_operands(x.dtype), jax.enable_x64(False):
        dx = megablox.gmm(dy, w, sizes, x.dtype, tiling, transpose_rhs=True)
        dw = megablox.tgmm(x.swapaxes(0, 1), dy, sizes, w.dtype, tiling)
    return dx, dw, None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array,
                   out_dtype=None) -> jax.Array:
    """``x[rows of group i] @ w[i]`` for x (m, k) sorted by group, w
    (g, k, n) and ``sizes`` (g,) int32, accumulated in float32; (m, n) in
    ``out_dtype`` (default x's).  Differentiable in x and w.  Rows past
    ``sum(sizes)`` of the result, and of x's gradient, are NOT written:
    mask them."""
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if _interpret(x, w):
        precision = None if x.dtype == jnp.float32 \
            else jax.lax.Precision.DEFAULT
        return jax.lax.ragged_dot(x, w, sizes, precision=precision,
                                  preferred_element_type=out_dtype)
    return _gmm_tpu(x, w, sizes.astype(jnp.int32), out_dtype)


# ---------------------------------------------------------------------------
# paged single-query decode attention, and the write beside it
# ---------------------------------------------------------------------------
#
# The serving decode path (mxnet_tpu/serving/decode.py) holds K/V in a
# fixed PAGE POOL of six axes (L, 2, P, H, rows, lanes): physical pages
# handed out by a host-side allocator, one logical sequence = a per-slot
# row of page ids.  A page's (page, D) tokens lie LANE-DENSE in it:
# ``pack = 128 // D`` tokens share one row of ``pack * D`` = 128 lanes
# (:func:`kv_pack`; token t of a page sits in row t // pack, lanes
# (t % pack) * D onward — the row-major reshape of (page, D)), so that
# D = 64 pads nothing and the TPU's own layout for the array is the
# row-major one a Mosaic kernel takes.  pack is 1 where D does not divide
# 128 or pack does not divide the page: rows and lanes are then
# (page, D) themselves.
#
# Inside the compiled step two kernels touch that pool, and both take it
# WHOLE, in the layout it arrives in, and address it through
# scalar-prefetched indices (PrefetchScalarGridSpec), so that XLA never
# slices, scatters into, transposes or copies anything pool-sized:
#
# * ``kv_write`` puts one token's K and V per slot at (layer, 0|1,
#   phys[s], :, token off[s]): grid (slot,), one (2, H, rows, lanes)
#   block in, the same block out under ``input_output_aliases`` — the
#   pool is updated where it lies.
# * ``decode_attn`` is ONE query token per slot against the pool: grid
#   (slot,), and a cell walks THE SLOT'S LIVE PAGES, G at a time.  The
#   pool stays in HBM (``memory_space=pl.ANY``); a group's pages — their
#   ids read from the scalar-prefetched table, ``ceil(seq_len / page)`` of
#   them and never a dead one — come in by the kernel's own asynchronous
#   copies, into one of two buffers while the other is worked on, and a
#   slot's last group starts the next slot's first, so the copies never
#   stop between cells.  HBM traffic is O(tokens_cached · D), and so is
#   the time: 72–76% of the byte roofline at the serve cell's contexts,
#   86–91% with every context full (v5e, PR 34).
#
#   Inside a group nothing is reduced further than it must be.  The one
#   cross-lane step is a token's score (the sum of k·q over its D lanes,
#   on the XLU; put back on the token's own lanes); scores, weights and
#   the accumulator keep the page's (H, rows, lanes) shape, so every row
#   and every token of a row runs a softmax OF ITS OWN over the pages
#   (running max / sum / accumulator in VMEM scratch), with one new
#   maximum and one rescale per GROUP, and the rows * pack partial
#   softmaxes are merged once, at the slot's end, the way split
#   flash-decode merges its parts.
#
#   Why not a page a cell (the form before PR 34: grid (slot, page), one
#   page by BlockSpec): measured on the v5e at the serve cell's geometry,
#   a cell cost 0.10 us to visit whether its page was live or dead (three
#   in four were dead: 2.5 of the step's 4.5 ms) and a live page 0.34 us
#   more, against 0.12 us for its 98 KB at 819 GB/s: the page's scores
#   went through (H, rows, 1) values, one lane in 128 of every register
#   they touched, with a rescale of the accumulator per 16 tokens and the
#   pipeline's prologue per cell.
#
#   G (:func:`_decode_pages_per_cell`) comes from the shapes: what fits a
#   VMEM budget, at most ``_DECODE_CELL_TOKENS`` tokens.  More pages a
#   group keep more bytes in flight (full contexts: 3.41 ms a step at
#   G = 8, 3.23 at 16) but a slot's last group works on its dead pages
#   too, masked (the cell's contexts: 1.13 / 1.00 / 1.05 ms at G = 4 / 8 /
#   16; one token a slot: 0.29 / 0.41 / 0.65).  Looping over the live
#   pages alone instead of unrolling the group lost a third (1.35 ms): the
#   XLU's latency then shows on every page.  The same lane sum as a
#   float32 product with a 0/1 matrix on the idle MXU: 1.41 ms.
#
# The layer rides as a scalar-prefetch value, not as a Python constant:
# the calls of every layer then share one Mosaic body.  A 4-D
# (P, H, page, D) pool — the public :func:`decode_attention` — is the
# six-axis one with two leading axes of length 1: one kernel, two ways in.

_LANES = 128


def kv_pack(page: int, head_dim: int) -> int:
    """Tokens that share one row of a pool page (1: none do)."""
    pack = _LANES // head_dim if _LANES % head_dim == 0 else 1
    return pack if page % pack == 0 else 1


def _lane_groups(shape, D, pack):
    """For each of the ``pack`` tokens of a row, the mask of its D lanes
    (None where a row is one token: nothing to mask)."""
    if pack == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return [(lane >= g * D) & (lane < (g + 1) * D) for g in range(pack)]


# What a cell of decode_attn may hold in VMEM: K and V of G pages, twice
# (the next group's copies land while this one is worked on) in the
# pool's dtype, and the group's scores once in float32.  A fifth of the
# 16 MiB a Mosaic kernel gets by default; the rest is the compiler's own
# (the spills of the unrolled group, the q and output blocks).
_DECODE_VMEM_BUDGET = 3 << 20
# and no more tokens than this to a group: the serve cell's best (above)
_DECODE_CELL_TOKENS = 128


def _decode_pages_per_cell(H, rows, lanes, D, itemsize, n_pages, pools=2,
                           cell_tokens=None):
    """G, the pages a ``decode_attn`` cell takes at a time: what fits
    ``_DECODE_VMEM_BUDGET``, at most ``cell_tokens`` tokens and at
    most the slot's whole table, at least one.  A function of the shapes
    the kernel sees and nothing else: 8 at GPT-2 small (H = 12, D = 64,
    page 16, float32: a 49 KB page), 2 at H = 32, D = 128 (262 KB).
    ``pools``: the pool operands a page is copied from (K and V; one for
    ``mla_attn``'s latent rows, which gives its own ``cell_tokens`` in
    ``_DECODE_CELL_TOKENS``' place)."""
    page_elems = H * rows * lanes
    per_page = 2 * pools * page_elems * itemsize + 4 * page_elems
    tokens = rows * (lanes // D)
    return max(1, min(_DECODE_VMEM_BUDGET // per_page,
                      (cell_tokens or _DECODE_CELL_TOKENS) // tokens,
                      n_pages))


def _decode_attn_kernel(pt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm,
                        o_ref, k_buf, v_buf, s_buf, m_ref, l_ref, acc_ref,
                        sems, buf_ref, *, G, D, v_at, scale, n_pages):
    """One slot: its live pages, G at a time (the block above has why).

    ``k_hbm`` / ``v_hbm`` are the whole pool(s), in HBM; ``k_buf`` /
    ``v_buf`` (2, G, H, rows, lanes) the two landing buffers, ``sems``
    (buffer, K|V) their copies' semaphores, ``buf_ref`` the buffer this
    slot's first group was sent to by the slot before.  ``s_buf`` keeps a
    group's scores between the pass that finds the group's maximum and
    the pass that weighs V.  Every value keeps the (H, rows, lanes) shape
    of a page: a one-token query against a page is a matrix-VECTOR
    product per head, which Mosaic's matmul does not take (it refused the
    batched ``(H,page,D)·(H,D)`` dot_general: "failed to parse
    TPU_DotDimensionNumbersAttr"), so the scores and the weighted sum are
    broadcast-multiplies on the VPU.  The query arrives repeated once per
    token of a row; each token's score is the sum over its own lanes."""
    s = pl.program_id(0)
    S = pl.num_programs(0)
    _, _, H, rows, lanes = k_buf.shape
    pack = lanes // D
    page = rows * pack
    layer = layer_ref[0]
    shape = (H, rows, lanes)

    def live_pages(slot):
        # at least one, so that an inactive slot's cell has a page (its
        # table's first entry, the trash page) and writes finite numbers.
        # lax.div: the operands are never negative, and the scalar core
        # pays for every sign it has to think about
        return jnp.maximum(jax.lax.div(len_ref[slot] + (page - 1), page), 1)

    def live_copies(slot, g, b, act):
        """``act`` (start or wait) on the K and the V copy of every live
        page of group ``g`` of ``slot`` into buffer ``b``: the same
        descriptors to start as to wait."""
        n = live_pages(slot)
        for i in range(G):
            j = g * G + i
            # a dead page's copy is never made, but its table entry is
            # read: keep the index inside the table's last group
            at = j if n_pages % G == 0 else jnp.minimum(j, n_pages - 1)
            phys = pt_ref[slot, at]
            ck = pltpu.make_async_copy(k_hbm.at[layer, 0, phys],
                                       k_buf.at[b, i], sems.at[b, 0])
            cv = pltpu.make_async_copy(v_hbm.at[layer, v_at, phys],
                                       v_buf.at[b, i], sems.at[b, 1])

            @pl.when(j < n)
            def _():
                act(ck)
                act(cv)

    def start(slot, g, b):
        live_copies(slot, g, b, lambda copy: copy.start())

    @pl.when(s == 0)
    def _first():
        # a dead page of a group keeps what its buffer held: weight 0
        # times that must be 0, so it must never be what VMEM woke up with
        v_buf[...] = jnp.zeros_like(v_buf)
        buf_ref[0] = 0
        start(0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, jnp.float32(_NEG_BIG))
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    n_live = live_pages(s)
    n_groups = jax.lax.div(n_live + (G - 1), G)
    b0 = buf_ref[0]
    q = q_ref[...].astype(jnp.float32)              # (H, 1, lanes)
    # the token of its page that a lane belongs to, and the lanes of each
    # of a row's tokens
    tok = (jax.lax.broadcasted_iota(jnp.int32, shape, 1) * pack
           + jax.lax.broadcasted_iota(jnp.int32, shape, 2) // D)
    groups = _lane_groups(shape, D, pack)

    def scores(b, i, left):
        """Page ``i`` of buffer ``b`` against the query: every lane of a
        token holds that token's score, a token past the slot's length
        the floor.  ``left``: the slot's tokens from this page on."""
        kq = k_buf[b, i].astype(jnp.float32) * q
        sc = None
        for mine in groups:
            s_g = jnp.sum(kq if mine is None else jnp.where(mine, kq, 0.0),
                          axis=-1, keepdims=True)           # (H, rows, 1)
            sc = (jnp.broadcast_to(s_g, shape) if sc is None
                  else jnp.where(mine, s_g, sc))
        return jnp.where(tok < left, sc * scale, jnp.float32(_NEG_BIG))

    def group(g, carry):
        b = (b0 + g) & 1
        last = g + 1 == n_groups
        nxt = jnp.where(last, s + 1, s)

        @pl.when(nxt < S)
        def _prefetch():
            start(jnp.minimum(nxt, S - 1), jnp.where(last, 0, g + 1), 1 - b)

        live_copies(s, g, b, lambda copy: copy.wait())

        # the scores of the whole group, then ONE new maximum for it
        left = len_ref[s] - g * (G * page)          # tokens from here on
        m_old = m_ref[...]
        m_new = m_old
        for i in range(G):
            sc = scores(b, i, left - i * page)
            s_buf[i] = sc
            m_new = jnp.maximum(m_new, sc)
        corr = jnp.exp(m_old - m_new)
        l_new = l_ref[...] * corr
        acc = acc_ref[...] * corr
        for i in range(G):
            p = jnp.exp(s_buf[i] - m_new)           # a token's weight, on
            l_new = l_new + p                       # its own lanes
            acc = acc + p * v_buf[b, i].astype(jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    buf_ref[0] = (b0 + n_groups) & 1

    # the rows and the tokens of a row each kept a softmax of their own:
    # merge them as split flash-decode merges its parts
    m = m_ref[...]
    m_all = jnp.max(jnp.max(m, axis=2, keepdims=True), axis=1, keepdims=True)
    w = jnp.exp(m - m_all)                          # (H, rows, lanes)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    l_all = jnp.sum(jnp.sum(jnp.where(lane % D == 0, l_ref[...] * w, 0.0),
                            axis=2, keepdims=True), axis=1, keepdims=True)
    # each token of a row has summed onto its own lanes; the caller folds
    # them (a (S, H, lanes) add, not worth a lane shuffle here)
    o_ref[...] = (jnp.sum(acc_ref[...] * w, axis=1, keepdims=True)
                  / jnp.maximum(l_all, jnp.float32(1e-37))
                  ).astype(o_ref.dtype)


def _layer_operand(layer):
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _decode_attn_pallas(q, k_pool, v_pool, layer, v_at, page_table,
                        seq_lens, scale, interpret):
    """``k_pool`` / ``v_pool``: six-axis ``(L, 2|1, P, H, rows, lanes)``
    operands (the same array twice in the serving step), left where they
    lie; K is read at ``[layer, 0]``, V at ``[layer, v_at]``."""
    H = q.shape[1]
    rows, lanes = k_pool.shape[4:]
    G = _decode_pages_per_cell(H, rows, lanes, q.shape[2],
                               k_pool.dtype.itemsize, page_table.shape[1])
    return _decode_attn_call(q, k_pool, v_pool, layer, page_table, seq_lens,
                             G=G, v_at=v_at, scale=scale,
                             interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("G", "v_at", "scale", "interpret"))
def _decode_attn_call(q, k_pool, v_pool, layer, page_table, seq_lens, *, G,
                      v_at, scale, interpret):
    """The kernel's call.  Under ``jax.jit`` for the trace's sake, not the
    call's: the layer is an operand, so every layer of a step is the same
    jitted call and the kernel's unrolled body is traced and lowered once
    a step, not once a layer (two seconds of set-up at twelve layers);
    XLA inlines the calls."""
    S, H, D = q.shape
    rows, lanes = k_pool.shape[4:]
    pack = lanes // D
    kern = functools.partial(_decode_attn_kernel, G=G, D=D, v_at=v_at,
                             scale=scale, n_pages=page_table.shape[1])
    # q and the output ride as (S, H, 1, ·): the block's trailing two dims
    # then equal the array's, and the kernel never reshapes
    row_block = pl.BlockSpec((None, H, 1, lanes),
                             lambda s, pt, ln, lyr: (s, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    state = pltpu.VMEM((H, rows, lanes), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[row_block, in_hbm, in_hbm],
        out_specs=row_block,
        scratch_shapes=[
            pltpu.VMEM((2, G, H, rows, lanes), k_pool.dtype),
            pltpu.VMEM((2, G, H, rows, lanes), v_pool.dtype),
            pltpu.VMEM((G, H, rows, lanes), jnp.float32),   # scores
            state, state, state,            # running max, sum, accumulator
            pltpu.SemaphoreType.DMA((2, 2)),                # buffer, K|V
            pltpu.SMEM((1,), jnp.int32),    # the buffer the slot starts in
        ],
    )
    q4 = jnp.tile(q.reshape(S, H, 1, D), (1, 1, 1, pack))
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=_out_struct((S, H, 1, lanes), q.dtype, q4, k_pool,
                                  v_pool),
            # a slot's first copies are started by the slot before it
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret, name="decode_attn",
        )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
          _layer_operand(layer), q4, k_pool, v_pool)
    return out.reshape(S, H, pack, D).sum(axis=2)


def _decode_attn_xla(q, k_pages, v_pages, page_table, seq_lens, scale):
    """XLA formulation: gather the slots' pages, mask, one softmax.  It
    materializes (S, max_pages·page, H·D) per call — fine on CPU and the
    form GSPMD can shard over a tp axis (pallas_call is a partitioning
    black box; the tp serving export always uses this path).  A pool of
    fewer heads than ``q`` (H = rep x H_kv) serves query head h from its
    head h // rep."""
    S, H, D = q.shape
    page, h_kv = k_pages.shape[2], k_pages.shape[1]
    n_pages = page_table.shape[1]
    T = n_pages * page
    # (S, n_pages, H_kv, page, D) -> (S, H_kv, T, D)
    k = k_pages[page_table].transpose(0, 2, 1, 3, 4).reshape(S, h_kv, T, D)
    v = v_pages[page_table].transpose(0, 2, 1, 3, 4).reshape(S, h_kv, T, D)
    if h_kv != H:
        k, v = (jnp.repeat(x, H // h_kv, axis=1) for x in (k, v))
    s_sht = jnp.einsum("shd,shtd->sht", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
    pos = jnp.arange(T, dtype=jnp.int32)[None, None, :]
    s_sht = jnp.where(pos < seq_lens[:, None, None].astype(jnp.int32),
                      s_sht, jnp.float32(_NEG_BIG))
    p = jax.nn.softmax(s_sht, axis=-1)
    out = jnp.einsum("sht,shtd->shd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_backend_is_pallas(S, H, D, page, dtype) -> bool:
    """``MXNET_TPU_PALLAS_DECODE``: ``1`` / ``0`` / ``auto`` (the
    ops/autotune cache's measured winner for this geometry, falling back
    to pallas on TPU and XLA elsewhere)."""
    knob = _pallas_decode_knob()
    if knob is not None:
        return knob
    from . import autotune as _autotune
    return _autotune.decode_backend(S, H, D, page, str(dtype)) == "pallas"


def _pallas_decode_knob():
    """``MXNET_TPU_PALLAS_DECODE`` as True / False, None for ``auto``."""
    knob = os.environ.get("MXNET_TPU_PALLAS_DECODE", "auto")
    return knob == "1" if knob in ("0", "1") else None


def _default_scale(D):
    return 1.0 / float(np.sqrt(D))


def decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     page_table: jax.Array, seq_lens: jax.Array,
                     scale=None, use_pallas=None) -> jax.Array:
    """Single-query flash attention against a paged KV cache.

    ``q``: (S, H, D) — one query token per decode slot; ``k_pages`` /
    ``v_pages``: (P, H, page, D) physical page pools; ``page_table``:
    (S, max_pages) int32 physical page id per (slot, logical page) —
    every entry must be a VALID pool index (unused entries point at the
    allocator's trash page); ``seq_lens``: (S,) int32 cached tokens per
    slot (0 = inactive slot, output is garbage-but-finite).  Returns
    (S, H, D).

    ``use_pallas``: None consults :func:`decode_backend_is_pallas`."""
    S, H, D = q.shape
    P, _, page, _ = k_pages.shape
    if scale is None:
        scale = _default_scale(D)
    if use_pallas is None:
        use_pallas = decode_backend_is_pallas(S, H, D, page, q.dtype)
    if not use_pallas:
        return _decode_attn_xla(q, k_pages, v_pages, page_table, seq_lens,
                                float(scale))
    pack = kv_pack(page, D)
    dense = (1, 1, P, H, page // pack, pack * D)
    return _decode_attn_pallas(q, k_pages.reshape(dense),
                               v_pages.reshape(dense), 0, 0, page_table,
                               seq_lens, float(scale),
                               _interpret(q, k_pages, v_pages))


def decode_attention_pool(q: jax.Array, kv: jax.Array, layer,
                          page_table: jax.Array, seq_lens: jax.Array,
                          scale=None) -> jax.Array:
    """:func:`decode_attention` for one layer of the whole serving pool
    ``kv`` (L, 2, P, H_kv, rows, lanes), which the kernel takes as it is:
    K at ``kv[layer, 0]``, V at ``kv[layer, 1]``, no slice made.  Pallas
    only (the XLA formulation takes the 4-D slices).

    ``q`` (S, H, D) has one query group a key/value head (H = rep x H_kv,
    head h reading head h // rep).  At rep 1 a slot's row is scored on the
    vector unit (the kernel above).  At rep > 1 the same name runs the
    ``chunk_attn`` body with one item a slot: the group's query heads are
    rows of ONE matrix product against the pages, the item two rows high
    (the slot's and a dead one), so that a bfloat16 query fills the
    16-row tile it packs into (PERF.md section 6)."""
    if scale is None:
        scale = _default_scale(q.shape[-1])
    S, H, D = q.shape
    if H == kv.shape[3]:
        return _decode_attn_pallas(q, kv, kv, layer, 1, page_table,
                                   seq_lens, float(scale), _interpret(q, kv))
    rows = jnp.stack([q, jnp.zeros_like(q)], axis=1).reshape(2 * S, H, D)
    limit = jnp.stack([seq_lens, jnp.zeros_like(seq_lens)], axis=1)
    out = _chunk_attn_call(
        rows, kv, layer, page_table,
        jnp.repeat(jnp.arange(S, dtype=jnp.int32), 2), limit.reshape(-1),
        TQ=2, scale=float(scale), interpret=_interpret(q, kv),
        cell_tokens=_GQA_CELL_TOKENS, name="decode_attn")
    return out.reshape(S, 2, H, D)[:, 0]


def _kv_write_kernel(phys_ref, off_ref, layer_ref, new_ref, kv_ref, out_ref,
                     *, D):
    """One row: its (2, H, rows, lanes) page with token ``off[r]`` replaced
    by the row's K and V (which arrive repeated once per token of a page
    row).  Rows that follow one another onto the same page (a chunk's
    consecutive positions) find it still in ``out_ref`` (the block index did
    not change, so nothing was written back or fetched again) and add their
    token to it."""
    del layer_ref                   # the index maps' business
    r = pl.program_id(0)
    off = off_ref[r]
    again = (r > 0) & (phys_ref[r] == phys_ref[jnp.maximum(r - 1, 0)])

    @pl.when(jnp.logical_not(again))
    def _():
        out_ref[...] = kv_ref[...]

    pack = kv_ref.shape[3] // D
    hit = jax.lax.broadcasted_iota(jnp.int32, kv_ref.shape, 2) == off // pack
    if pack > 1:                    # several tokens a row: this one's lanes
        lane = jax.lax.broadcasted_iota(jnp.int32, kv_ref.shape, 3)
        first = off % pack * D
        hit = hit & (lane >= first) & (lane < first + D)
    out_ref[...] = jnp.where(hit, new_ref[...], out_ref[...])


def kv_write(kv: jax.Array, layer, k: jax.Array, v: jax.Array,
             phys: jax.Array, off: jax.Array, use_pallas=True) -> jax.Array:
    """The pool ``kv`` (L, 2, P, H, rows, lanes) with token ``off[r]`` of
    page ``phys[r]`` of ``layer`` set to ``k[r]`` (at ``[layer, 0]``) and
    ``v[r]`` (``[layer, 1]``) for every row ``r`` — written where the
    pool lies (``input_output_aliases``; donate ``kv`` and nothing
    pool-sized is copied).  ``k`` / ``v``: (R, H, D), a row a slot's token
    or a chunk's; H is the pool's (key/value) heads, D its head width, the
    values cast to the pool's dtype.  Rows bound for one live page must
    follow one another; rows that share a page otherwise (the dead ones,
    all on the trash page) may overwrite one another there in any order.
    ``use_pallas=False``: the XLA scatter over the pool seen by token."""
    if not use_pallas:
        L, _, P, H, rows, lanes = kv.shape
        D = k.shape[-1]
        by_token = kv.reshape(L, 2, P, H, rows * lanes // D, D)
        by_token = by_token.at[layer, 0, phys, :, off, :].set(
            k.astype(kv.dtype))
        by_token = by_token.at[layer, 1, phys, :, off, :].set(
            v.astype(kv.dtype))
        return by_token.reshape(kv.shape)
    return _kv_write_call(kv, layer, k, v, phys, off,
                          interpret=_interpret(kv, k, v))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_write_call(kv, layer, k, v, phys, off, *, interpret):
    """The kernel's call, under ``jax.jit`` with the layer an operand (as
    ``_decode_attn_call``): every layer of a step is the same call, traced
    and lowered once a step."""
    R, H, D = k.shape
    rows, lanes = kv.shape[4:]
    new = jnp.tile(jnp.stack([k, v], axis=1).astype(kv.dtype)
                   .reshape(R, 2, H, 1, D), (1, 1, 1, 1, lanes // D))
    page_block = pl.BlockSpec(
        (None, 2, None, H, rows, lanes),
        lambda r, ph, of, lyr: (lyr[0], 0, ph[r], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((None, 2, H, 1, lanes),
                         lambda r, ph, of, lyr: (r, 0, 0, 0, 0)),
            page_block,
        ],
        out_specs=page_block,
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_kv_write_kernel, D=D), grid_spec=grid_spec,
            out_shape=_out_struct(kv.shape, kv.dtype, new, kv),
            # operand 4 (after the three scalars and the rows) is the pool
            input_output_aliases={4: 0},
            # a row may find its page in the output the row before left
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret, name="kv_write",
        )(phys.astype(jnp.int32), off.astype(jnp.int32),
          _layer_operand(layer), new, kv)


# ---------------------------------------------------------------------------
# chunk attention: many prompt rows of a slot against the six-axis pool
# ---------------------------------------------------------------------------
#
# A many-token step (``DecodeProgram`` with ``prefill_tokens_per_step``) has,
# beside the slots' own rows that ``decode_attn`` serves, a chunk of prompt
# rows in blocks of :func:`chunk_attn_rows`, the live rows of a block one
# slot's consecutive positions (the block's first row names the slot).
# ``chunk_attn`` walks ITEMS, one a block: the slot's live pages, G at a time
# (:func:`_decode_pages_per_cell`), by the kernel's own double-buffered
# copies out of the pool left in HBM, an item's last group starting the next
# live item's first, exactly as ``decode_attn`` walks a slot.  So a block of
# rows reads its slot's context ONCE, where one row a slot would read it once
# a row.  A block with no live row is skipped: no copy, no product.
#
# Per head, scores and the weighted sum are two MXU products a group, in
# float32 (the package's ``highest``), the softmax the online one with one
# running max / sum a query row, causal by each row's own limit.  A page row
# holds ``pack`` tokens side by side on the lanes (``kv_pack``: two at
# D = 64), so the query arrives once per token of a row, each copy on that
# token's lanes and zero elsewhere, the copies stacked down the sublanes:
# (pack * rows, lanes) against the page rows (G * rows_of_a_page, lanes)
# gives every token's score in the copy of its parity, and the weighted sum
# of V's rows in that copy holds the answer on the parity's own lanes, which
# is where the row's accumulator takes it from.  Both products are ONE
# batched product over the heads (a group's pages lie head by head in the
# landing buffers), so that the MXU works on one head while the vector unit
# works on another: 1,496 bundles a group of 8 pages at GPT-2 small's shapes,
# compiled for the v5e, against 3,331 with a loop over the heads; and the
# kernel's body is small, which the step's set-up pays for in tracing and
# lowering it (PERF.md, PR 39).
#
# A pool of H_kv heads serving H = rep x H_kv query heads (grouped-query
# attention) is the same body: the rep query heads of a group are more rows
# of the item's matrix (a row and a head of its group to a matrix row), so
# one product a group scores them all against the pages they share, and the
# pool's dtype (bfloat16) is the operands'.  ``decode_attn`` of such a pool
# is this body with one item a slot (:func:`decode_attention_pool`).

_CHUNK_ROWS = 16        # query rows of a chunk_attn item
# tokens a group of pages holds at most where the pool serves query groups:
# a group's query heads are rows of the item's products, so its pages are
# read once for them all and a larger group amortises the walk
_GQA_CELL_TOKENS = 512


def chunk_attn_rows() -> int:
    """Rows of one ``chunk_attn`` item: the host lays a slot's chunk rows out
    in blocks of this many (the last padded with dead rows)."""
    return _CHUNK_ROWS


def _chunk_attn_kernel(slot_ref, ctx_ref, layer_ref, pt_ref, q_ref, lim_ref,
                       k_hbm, v_hbm, o_ref, k_buf, v_buf, m_ref, l_ref,
                       acc_ref, sems, buf_ref, *, G, D):
    """One item (the block above has what an item is).  ``slot_ref`` /
    ``ctx_ref``: the item's slot and the positions its last live row attends
    (0: a dead item); ``q_ref`` (H, pack * TQ, lanes) the block's queries,
    scaled, one copy a parity; ``lim_ref`` (pack * TQ, 1) each copy's limit
    less its parity (a token at ``pack * n + base`` counts where that is
    below it); ``k_buf`` / ``v_buf`` (2, H, G, rows, lanes) the two landing
    buffers, a head's pages of a group side by side.  With a query group a
    key/value head, H counts the pool's heads and a "query row" here is
    one (row, query head of the group) pair: the caller folds them."""
    i = pl.program_id(0)
    N = pl.num_programs(0)
    _, H, _, rows, lanes = k_buf.shape
    pack = lanes // D
    page = rows * pack
    T = G * rows                                    # page rows of a group
    M = q_ref.shape[1]
    TQ = M // pack
    layer = layer_ref[0]
    # bfloat16 operands go to the MXU as they are (_mxu_dot has why)
    precision = None if k_buf.dtype == jnp.float32 \
        else jax.lax.Precision.DEFAULT

    def live_pages(item):
        # none for a dead item: it copies nothing
        return jax.lax.div(ctx_ref[item] + (page - 1), page)

    def live_copies(item, g, b, act):
        """``act`` (start or wait) on the K and the V copy of every live
        page of group ``g`` of ``item`` into buffer ``b``: the same
        descriptors to start as to wait."""
        slot = slot_ref[item]

        def page_copies(k, carry):
            phys = pt_ref[slot, g * G + k]
            act(pltpu.make_async_copy(k_hbm.at[layer, 0, phys],
                                      k_buf.at[b, :, k], sems.at[b, 0]))
            act(pltpu.make_async_copy(v_hbm.at[layer, 1, phys],
                                      v_buf.at[b, :, k], sems.at[b, 1]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(live_pages(item) - g * G, 0, G),
                          page_copies, 0)

    def start(item, g, b):
        live_copies(item, g, b, lambda copy: copy.start())

    @pl.when(i == 0)
    def _first():
        # a dead page of a group keeps what its buffer held: weight 0 times
        # that must be 0, so it must never be what VMEM woke up with
        v_buf[...] = jnp.zeros_like(v_buf)
        buf_ref[0] = 0

    n_groups = jax.lax.div(live_pages(i) + (G - 1), G)
    # a live item's first copies were started by the item before it, where
    # that one was live; otherwise it starts them itself
    after_live = (i > 0) & (ctx_ref[jnp.maximum(i - 1, 0)] > 0)

    @pl.when((n_groups > 0) & jnp.logical_not(after_live))
    def _own_start():
        start(i, 0, buf_ref[0])

    b0 = buf_ref[0]
    # a dead item walks no group and answers zeros
    m_ref[...] = jnp.full_like(m_ref, jnp.float32(_NEG_BIG))
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    lim = lim_ref[...]                                          # (M, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (M, T), 1) * pack
    # a query row's copies, one a parity of a page row's tokens
    parts = [slice(p * TQ, (p + 1) * TQ) for p in range(pack)]
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 2) // D

    def group(g, carry):
        b = (b0 + g) & 1
        last = g + 1 == n_groups
        nxt = jnp.where(last, i + 1, i)

        @pl.when(nxt < N)
        def _prefetch():
            start(jnp.minimum(nxt, N - 1), jnp.where(last, 0, g + 1),
                  1 - b)

        live_copies(i, g, b, lambda copy: copy.wait())
        valid = col + g * (G * page) < lim                      # (M, T)

        # every head's scores, one softmax step for all of them, then
        # every head's weighted sum
        s = jax.lax.dot_general(
            q_ref[...], k_buf[b].reshape(H, T, lanes),
            (((2,), (2,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32)                 # (H, M, T)
        s = jnp.where(valid[None], s, jnp.float32(_NEG_BIG))
        # one running max and sum a query row, over its copies: each
        # copy's weighted sum lands on its own parity's lanes
        row_max = jnp.max(s, axis=2, keepdims=True)             # (H, M, 1)
        m_old = m_ref[...]                                      # (H, TQ, 1)
        m_new = m_old
        for part in parts:
            m_new = jnp.maximum(m_new, row_max[:, part])
        p = jnp.exp(s - jnp.concatenate([m_new] * pack, axis=1))
        corr = jnp.exp(m_old - m_new)
        sums = jnp.sum(p, axis=2, keepdims=True)
        l_new = l_ref[...] * corr
        for part in parts:
            l_new = l_new + sums[:, part]
        pv = jax.lax.dot_general(
            p.astype(v_buf.dtype), v_buf[b].reshape(H, T, lanes),
            (((2,), (1,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32)                 # (H, M, lanes)
        mine = pv[:, parts[0]]
        for k, part in enumerate(parts[1:], 1):
            mine = jnp.where(lane == k, pv[:, part], mine)
        acc_ref[...] = acc_ref[...] * corr + mine
        l_ref[...] = l_new
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...],
                                             jnp.float32(1e-37))
                  ).astype(o_ref.dtype)

    buf_ref[0] = (b0 + n_groups) & 1


@functools.partial(jax.jit, static_argnames=("TQ", "scale", "interpret",
                                             "cell_tokens", "name"))
def _chunk_attn_call(q, kv, layer, page_table, row_slot, row_limit, *, TQ,
                     scale, interpret, cell_tokens=None, name="chunk_attn"):
    """The kernel's call, under ``jax.jit`` with the layer an operand, so
    that the layers of a step share one traced and lowered body (as
    ``_decode_attn_call``).  ``q`` (C, H, D) over a pool of H_kv heads: the
    ``rep = H / H_kv`` query heads of a group become rows of the item's
    matrix, ``(row, head of the group)`` in that order, queries in the
    pool's dtype.  ``cell_tokens`` / ``name``: the group size's cap
    (:func:`_decode_pages_per_cell`) and the kernel's name."""
    C, H, D = q.shape
    h_kv, rows, lanes = kv.shape[3:]
    rep = H // h_kv
    pack = lanes // D
    NB, M = C // TQ, pack * TQ * rep
    G = _decode_pages_per_cell(h_kv, rows, lanes, D, kv.dtype.itemsize,
                               page_table.shape[1], cell_tokens=cell_tokens)
    limit = row_limit.astype(jnp.int32).reshape(NB, TQ)
    item_slot = row_slot.astype(jnp.int32).reshape(NB, TQ)[:, 0]
    item_ctx = jnp.max(limit, axis=1)
    # (NB, H_kv, pack * TQ * rep, lanes): copy p of a row's query on the
    # lanes of a page row's token p, zero on the others
    qs = (q.astype(jnp.float32) * scale).reshape(NB, TQ, h_kv, rep * D) \
        .transpose(0, 2, 1, 3).reshape(NB, h_kv, TQ * rep, D)
    qp = jnp.concatenate(
        [jnp.pad(qs, ((0, 0), (0, 0), (0, 0), (p * D, lanes - (p + 1) * D)))
         for p in range(pack)], axis=2).astype(kv.dtype)
    if rep > 1:
        limit = jnp.repeat(limit, rep, axis=1)
    lim = jnp.concatenate([limit - p for p in range(pack)], axis=1)[..., None]
    kern = functools.partial(_chunk_attn_kernel, G=G, D=D)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(NB,),
        in_specs=[pl.BlockSpec((None, h_kv, M, lanes),
                               lambda i, *_: (i, 0, 0, 0)),
                  pl.BlockSpec((None, M, 1), lambda i, *_: (i, 0, 0)),
                  in_hbm, in_hbm],
        out_specs=pl.BlockSpec((None, h_kv, TQ * rep, lanes),
                               lambda i, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, h_kv, G, rows, lanes), kv.dtype),
            pltpu.VMEM((2, h_kv, G, rows, lanes), kv.dtype),
            pltpu.VMEM((h_kv, TQ * rep, 1), jnp.float32),   # running max
            pltpu.VMEM((h_kv, TQ * rep, 1), jnp.float32),   # running sum
            pltpu.VMEM((h_kv, TQ * rep, lanes), jnp.float32),  # accumulator
            pltpu.SemaphoreType.DMA((2, 2)),        # buffer, K|V
            pltpu.SMEM((1,), jnp.int32),    # the buffer the item starts in
        ],
    )
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=_out_struct((NB, h_kv, TQ * rep, lanes), jnp.float32,
                                  qp, kv),
            # an item's first copies are started by the item before it
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret, name=name,
        )(item_slot, item_ctx, _layer_operand(layer),
          page_table.astype(jnp.int32), qp, lim, kv, kv)
    out = out.reshape(NB, h_kv, TQ * rep, pack, D).sum(axis=3) \
        .reshape(NB, h_kv, TQ, rep * D)
    return out.transpose(0, 2, 1, 3).reshape(C, H, D).astype(q.dtype)


def chunk_attention(q: jax.Array, kv: jax.Array, layer,
                    page_table: jax.Array, row_slot: jax.Array,
                    row_limit: jax.Array, scale=None,
                    use_pallas=None) -> jax.Array:
    """Attention of a step's chunk rows against their slots' pages in layer
    ``layer`` of the whole serving pool ``kv`` (L, 2, P, H_kv, rows, lanes),
    taken as it lies.  ``q``: (C, H, D), H = rep x H_kv query heads, head h
    reading the pool's head h // rep; C whole blocks of
    :func:`chunk_attn_rows` whose live rows are one slot's, the slot that
    ``row_slot`` names for the block's first row; ``row_limit`` (C,): the
    positions a row attends (those below it; 0: a dead row, whose output is
    finite and unused).  Returns (C, H, D).  ``use_pallas``: None consults
    :func:`decode_backend_is_pallas`; the XLA formulation gathers each row's
    slot's pages."""
    C, H, D = q.shape
    if scale is None:
        scale = _default_scale(D)
    L, _, P, h_kv, rows, lanes = kv.shape
    page = rows * (lanes // D)
    if use_pallas is None:
        use_pallas = decode_backend_is_pallas(C, H, D, page, q.dtype)
    if not use_pallas:
        by_token = kv.reshape(L, 2, P, h_kv, page, D)[layer]
        return _decode_attn_xla(q, by_token[0], by_token[1],
                                page_table[row_slot], row_limit, float(scale))
    if C % _CHUNK_ROWS:
        raise ValueError("chunk_attention: %d rows are not whole blocks of %d"
                         % (C, _CHUNK_ROWS))
    return _chunk_attn_call(q, kv, layer, page_table, row_slot, row_limit,
                            TQ=_CHUNK_ROWS, scale=float(scale),
                            interpret=_interpret(q, kv),
                            cell_tokens=None if H == h_kv
                            else _GQA_CELL_TOKENS)


# ---------------------------------------------------------------------------
# latent attention (MLA) against a pool of latent rows, and the write beside it
# ---------------------------------------------------------------------------
#
# A model with multi-head latent attention caches ONE row a token a layer,
# ``z = [c ; k_r]`` (the normed latent and the rope key shared by every
# head), and in the absorbed form a query head is a row ``[W_UK^T q_n ; q_r]``
# of the same width: every head of a query row scores the SAME cached rows,
# and the value is the row's first ``latent`` lanes.  The pool is
# ``(L, P, page, W)``: a page's tokens are its rows, W the row's width
# (latent + rope = 576) rounded up to whole tiles of 128 lanes
# (:func:`latent_row_lanes`: 640, the last 64 lanes zero).  The TPU tiles the
# minor axis by 128 whatever the shape says, so a 576-wide array takes the
# same 640 in HBM; saying so in the shape makes the device's own layout the
# row-major one and a page an aligned (page, W) block that the kernels' copies
# may slice (Mosaic refuses a copy out of a padded 576).
#
# A step's query rows come in two kinds under one fixed budget: rows
# ``[0, n_decode)`` are one row a slot (row i belongs to slot i), the rest is
# the prefill chunk in blocks of :func:`mla_chunk_rows` rows, the live rows of
# a block all of one slot.  ``mla_attn`` walks ITEMS: a decode row, or a chunk
# block.  An item stacks its rows' heads into one matrix (16 rows x 64 heads =
# M of 1,024 x W) and walks its slot's live pages G at a time exactly as
# ``decode_attn`` does (pool in HBM, the kernel's own double-buffered copies,
# an item's last group starting the next item's first; G from
# :func:`_decode_pages_per_cell`): scores ``Q Z^T`` and the weighted sum
# ``P Z[:, :latent]`` are two MXU products a group, the softmax is the online
# one in float32, causal inside a chunk by each row's own limit.  A decode
# item's M is the 64 heads of its one row: its time is the copy of its pages.
# A slot's pages are read once for each of its items: once for a decoding
# slot, once per ``mla_chunk_rows`` rows of a chunk, whose items are bound by
# the MXU and not by those copies.

_MLA_CELL_TOKENS = 512      # tokens a group of pages holds at most
_MLA_CHUNK_ROWS = 16        # query rows of a chunk item
_MLA_VMEM_LIMIT = 48 << 20


def latent_row_lanes(width: int) -> int:
    """Lanes a latent row of ``width`` takes in the pool: whole tiles."""
    return -(-int(width) // _LANES) * _LANES


def _pad_lanes(x, lanes):
    """``x`` with zeros appended to its last axis up to ``lanes``."""
    short = lanes - x.shape[-1]
    if short == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)])


def mla_chunk_rows() -> int:
    """Rows of one chunk item: the host lays a slot's chunk rows out in
    blocks of this many (the last padded with dead rows)."""
    return _MLA_CHUNK_ROWS


def _mla_attn_kernel(slot_ref, ctx_ref, layer_ref, pt_ref, qd_ref, qc_ref,
                     ld_ref, lc_ref, pool_hbm, od_ref, oc_ref, z_buf, m_ref,
                     l_ref, acc_ref, sems, buf_ref, *, n_decode, G, page,
                     latent, scale, n_pages):
    """One item (the block above has what an item is).  ``slot_ref`` /
    ``ctx_ref``: the item's slot and the positions its last live row
    attends; ``qd_ref`` (H, W) / ``qc_ref`` (rows*H, W) the decode row's and
    the chunk block's queries, ``ld_ref`` / ``lc_ref`` each matrix row's
    limit (it attends positions below it; 0: a dead row, whose output is
    finite and unused); ``z_buf`` (2, G*page, W) the two landing buffers."""
    i = pl.program_id(0)
    N = pl.num_programs(0)
    T = G * page
    layer = layer_ref[0]

    def live_pages(item):
        # at least one, as in decode_attn: an idle item has a page to read
        return jnp.maximum(jax.lax.div(ctx_ref[item] + (page - 1), page), 1)

    def live_copies(item, g, b, act):
        slot = slot_ref[item]
        n = live_pages(item)
        for k in range(G):
            j = g * G + k
            at = j if n_pages % G == 0 else jnp.minimum(j, n_pages - 1)
            copy = pltpu.make_async_copy(
                pool_hbm.at[layer, pt_ref[slot, at]],
                z_buf.at[b, pl.ds(k * page, page)], sems.at[b])

            @pl.when(j < n)
            def _():
                act(copy)

    def start(item, g, b):
        live_copies(item, g, b, lambda copy: copy.start())

    @pl.when(i == 0)
    def _first():
        # a dead page keeps what its buffer held, and weight 0 times that
        # must be 0: never what VMEM woke up with
        z_buf[...] = jnp.zeros_like(z_buf)
        buf_ref[0] = 0
        start(0, 0, 0)

    n_groups = jax.lax.div(live_pages(i) + (G - 1), G)
    b0 = buf_ref[0]

    def walk(q_ref, lim_ref, o_ref):
        M = q_ref.shape[0]
        m_ref[:M] = jnp.full((M, 1), _NEG_BIG, jnp.float32)
        l_ref[:M] = jnp.zeros((M, 1), jnp.float32)
        acc_ref[:M] = jnp.zeros((M, latent), jnp.float32)
        q = q_ref[...]
        limit = lim_ref[...]                                # (M, 1)

        def group(g, carry):
            b = (b0 + g) & 1
            last = g + 1 == n_groups
            nxt = jnp.where(last, i + 1, i)

            @pl.when(nxt < N)
            def _prefetch():
                start(jnp.minimum(nxt, N - 1), jnp.where(last, 0, g + 1),
                      1 - b)

            live_copies(i, g, b, lambda copy: copy.wait())
            z = z_buf[b]                                    # (T, W)
            s = _mxu_dot(q, z, _NT) * scale                 # (M, T)
            pos = g * T + jax.lax.broadcasted_iota(jnp.int32, (M, T), 1)
            s = jnp.where(pos < limit, s, jnp.float32(_NEG_BIG))
            m_old = m_ref[:M]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_old - m_new)
            l_ref[:M] = l_ref[:M] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:M] = acc_ref[:M] * corr + _mxu_dot(
                p.astype(z.dtype), z[:, :latent], _NN)
            m_ref[:M] = m_new
            return carry

        jax.lax.fori_loop(0, n_groups, group, 0)
        o_ref[...] = (acc_ref[:M] / jnp.maximum(l_ref[:M], jnp.float32(1e-37))
                      ).astype(o_ref.dtype)

    @pl.when(i < n_decode)
    def _decode_row():
        walk(qd_ref, ld_ref, od_ref)

    @pl.when(i >= n_decode)
    def _chunk_block():
        walk(qc_ref, lc_ref, oc_ref)

    buf_ref[0] = (b0 + n_groups) & 1


@functools.partial(jax.jit, static_argnames=("n_decode", "latent", "scale",
                                             "interpret"))
def _mla_attn_call(q, pool, layer, page_table, row_slot, row_limit, *,
                   n_decode, latent, scale, interpret):
    """The kernel's call, under ``jax.jit`` so that the layers of a step
    share one traced body (as ``_decode_attn_call``)."""
    R, H, W = q.shape
    page = pool.shape[2]
    S, TQ = n_decode, _MLA_CHUNK_ROWS
    NB = (R - S) // TQ
    G = _decode_pages_per_cell(1, page, W, W, pool.dtype.itemsize,
                               page_table.shape[1], pools=1,
                               cell_tokens=_MLA_CELL_TOKENS)
    row_limit = row_limit.astype(jnp.int32)
    chunk_limit = row_limit[S:].reshape(NB, TQ)
    item_slot = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                                 row_slot[S:].astype(jnp.int32)
                                 .reshape(NB, TQ)[:, 0]])
    item_ctx = jnp.concatenate([row_limit[:S], jnp.max(chunk_limit, axis=1)])
    by_head = jnp.broadcast_to(row_limit[:, None, None], (R, H, 1))
    kern = functools.partial(_mla_attn_kernel, n_decode=S, G=G, page=page,
                             latent=latent, scale=scale,
                             n_pages=page_table.shape[1])

    def decode_block(width):
        return pl.BlockSpec((None, H, width),
                            lambda i, *_: (jnp.minimum(i, S - 1), 0, 0))

    def chunk_block(width):
        return pl.BlockSpec((None, TQ * H, width),
                            lambda i, *_: (jnp.maximum(i - S, 0), 0, 0))

    M = TQ * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S + NB,),
        in_specs=[decode_block(W), chunk_block(W), decode_block(1),
                  chunk_block(1), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[decode_block(latent), chunk_block(latent)],
        scratch_shapes=[
            pltpu.VMEM((2, G * page, W), pool.dtype),
            pltpu.VMEM((M, 1), jnp.float32),        # running max
            pltpu.VMEM((M, 1), jnp.float32),        # running sum
            pltpu.VMEM((M, latent), jnp.float32),   # accumulator
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),    # the buffer the item starts in
        ],
    )
    with jax.enable_x64(False):
        ud, uc = pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=[_out_struct((S, H, latent), q.dtype, q, pool),
                       _out_struct((NB, M, latent), q.dtype, q, pool)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_MLA_VMEM_LIMIT),
            interpret=interpret, name="mla_attn",
        )(item_slot, item_ctx, _layer_operand(layer),
          page_table.astype(jnp.int32), q[:S], q[S:].reshape(NB, M, W),
          by_head[:S], by_head[S:].reshape(NB, M, 1), pool)
    return jnp.concatenate([ud, uc.reshape(NB * TQ, H, latent)])


def _mla_attn_xla(q, pool, layer, page_table, row_slot, row_limit, latent,
                  scale):
    """XLA formulation: gather each row's slot's pages, mask, one softmax.
    It materialises (R, max_pages * page, W): the CPU tests' form."""
    zs = pool[layer][page_table]                    # (S, pages, page, W)
    zs = zs.reshape(zs.shape[0], -1, zs.shape[-1])[row_slot]
    zs = zs.astype(jnp.float32)                     # (R, T, W)
    s = jnp.einsum("rhw,rtw->rht", q.astype(jnp.float32), zs,
                   precision=jax.lax.Precision.HIGHEST) * scale
    pos = jnp.arange(zs.shape[1], dtype=jnp.int32)[None, None, :]
    s = jnp.where(pos < row_limit[:, None, None].astype(jnp.int32), s,
                  jnp.float32(_NEG_BIG))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("rht,rtc->rhc", p, zs[..., :latent],
                      precision=jax.lax.Precision.HIGHEST).astype(q.dtype)


def pool_ops_are_pallas() -> bool:
    """``MXNET_TPU_PALLAS_DECODE`` for the latent pool's two kernels: ``1``
    / ``0``, else (``auto``) Pallas on a TPU and XLA elsewhere."""
    knob = _pallas_decode_knob()
    return jax.default_backend() == "tpu" if knob is None else knob


def mla_attention(q: jax.Array, pool: jax.Array, layer,
                  page_table: jax.Array, row_slot: jax.Array,
                  row_limit: jax.Array, *, n_decode: int, latent: int,
                  scale: float, use_pallas=None) -> jax.Array:
    """Absorbed latent attention of a step's query rows against the latent
    pool ``(L, P, page, W)`` of ``layer``.

    ``q``: (R, H, width), a head's ``[W_UK^T q_n ; q_r]``, padded here to
    the pool's W lanes; ``page_table``:
    (S, max_pages) valid page ids; ``row_slot``: (R,) the slot whose pages a
    row reads; ``row_limit``: (R,) the positions a row attends (those below
    it; 0 for a dead row).  Rows ``[0, n_decode)`` are one a slot (row i of
    slot i), the others lie in blocks of :func:`mla_chunk_rows` whose live
    rows share the slot the block's first row names.  Returns ``u``
    (R, H, latent): the softmax-weighted sum of the rows' first ``latent``
    lanes, before the value up-projection."""
    if use_pallas is None:
        use_pallas = pool_ops_are_pallas()
    q = _pad_lanes(q, pool.shape[-1])
    if not use_pallas:
        return _mla_attn_xla(q, pool, layer, page_table, row_slot, row_limit,
                             latent, float(scale))
    if (q.shape[0] - n_decode) % _MLA_CHUNK_ROWS or q.shape[0] <= n_decode:
        raise ValueError("mla_attention: %d chunk rows are not whole blocks "
                         "of %d" % (q.shape[0] - n_decode, _MLA_CHUNK_ROWS))
    return _mla_attn_call(q, pool, layer, page_table, row_slot, row_limit,
                          n_decode=int(n_decode), latent=int(latent),
                          scale=float(scale), interpret=_interpret(q, pool))


def _latent_write_kernel(phys_ref, off_ref, layer_ref, new_ref, pool_ref,
                         out_ref, *, tile):
    """One row: the ``tile`` rows of its page that hold offset ``off[r]``,
    with that row replaced.  Rows that follow one another into the same tile
    (a chunk's consecutive positions) find it still in ``out_ref`` (the block
    index did not change, so nothing was written back or fetched again) and
    add their row to it."""
    del layer_ref
    r = pl.program_id(0)
    before = jnp.maximum(r - 1, 0)
    off = off_ref[r]
    again = ((r > 0) & (phys_ref[r] == phys_ref[before])
             & (off // tile == off_ref[before] // tile))

    @pl.when(jnp.logical_not(again))
    def _():
        out_ref[...] = pool_ref[...]

    hit = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0) == off % tile
    out_ref[...] = jnp.where(hit, new_ref[...], out_ref[...])


def _latent_write_pallas(pool, layer, z, phys, off, interpret):
    R, W = z.shape
    page = pool.shape[2]
    tile = min(_sublane_tile(pool.dtype), page)
    tile_block = pl.BlockSpec(
        (None, None, tile, W),
        lambda r, ph, of, lyr: (lyr[0], ph[r], of[r] // tile, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R,),
        in_specs=[pl.BlockSpec((None, 1, W), lambda r, *_: (r, 0, 0)),
                  tile_block],
        out_specs=tile_block,
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_latent_write_kernel, tile=tile),
            grid_spec=grid_spec,
            out_shape=_out_struct(pool.shape, pool.dtype, z, pool),
            # operand 4 (after the three scalars and the rows) is the pool
            input_output_aliases={4: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret, name="latent_write",
        )(phys.astype(jnp.int32), off.astype(jnp.int32),
          _layer_operand(layer), z.astype(pool.dtype).reshape(R, 1, W), pool)


def latent_write(pool: jax.Array, layer, z: jax.Array, phys: jax.Array,
                 off: jax.Array, use_pallas=None) -> jax.Array:
    """The latent pool ``(L, P, page, W)`` with row ``off[r]`` of page
    ``phys[r]`` of ``layer`` set to ``z[r]`` for every row ``r`` of ``z``
    (R, W) — written where the pool lies (``input_output_aliases``).  Rows
    bound for one page must follow one another; dead rows (all on the trash
    page) may overwrite one another there in any order."""
    if use_pallas is None:
        use_pallas = pool_ops_are_pallas()
    z = _pad_lanes(z, pool.shape[-1])
    if not use_pallas:
        return pool.at[layer, phys, off].set(z.astype(pool.dtype))
    return _latent_write_pallas(pool, layer, z, phys, off,
                                _interpret(pool, z))


# ---------------------------------------------------------------------------
# weight-only quantized matmul (int8 / packed int4, per-channel scales)
# ---------------------------------------------------------------------------
#
# The decode hot loop is weights-bandwidth-bound: every token re-reads
# every matmul weight once.  Weight-only quantization (the
# two_bit_compress kernel above is the in-repo template for fused
# quantize/dequantize passes) cuts that HBM traffic 4x (int8) / 8x
# (int4) with dequantization FUSED into the matmul kernel — the f32
# weights never exist in HBM.  Scales are per output channel, the
# granularity at which FC weights are row-scaled (y = x @ W.T).

_QMAX = {8: 127, 4: 7}


def quantize_weight(w, bits: int = 8):
    """Quantize an FC weight (N, K) -> (qw, scales) with per-output-
    channel (per-row) scales.  int8: ``qw`` is (N, K) int8.  int4:
    ``qw`` is (N, K//2) uint8 with two nibbles per byte (K padded to
    even; low nibble = even k, high nibble = odd k), values in [-7, 7].
    Dequantization is ``w ≈ qw * scales[:, None]``."""
    if bits not in _QMAX:
        raise ValueError("quantize_weight: bits must be 8 or 4, got %r"
                         % (bits,))
    w = np.asarray(w, np.float32)
    if w.ndim != 2:
        raise ValueError("quantize_weight wants a 2-D FC weight, got %s"
                         % (w.shape,))
    qmax = _QMAX[bits]
    scales = np.max(np.abs(w), axis=1) / qmax
    scales = np.where(scales == 0, 1.0, scales).astype(np.float32)
    q = np.clip(np.rint(w / scales[:, None]), -qmax, qmax)
    if bits == 8:
        return q.astype(np.int8), scales
    if w.shape[1] % 2:
        q = np.concatenate([q, np.zeros((w.shape[0], 1), q.dtype)], axis=1)
    lo = q[:, 0::2].astype(np.int64) & 0xF
    hi = q[:, 1::2].astype(np.int64) & 0xF
    return ((hi << 4) | lo).astype(np.uint8), scales


def _nibbles(packed):
    """(N, K//2) uint8 -> the (N, K//2) f32 low (even k) and high (odd k)
    nibbles, sign-extended to [-7, 7]."""
    p = packed.astype(jnp.int32)

    def signed(n):
        return jnp.where(n > 7, n - 16, n).astype(jnp.float32)
    return signed(p & 0xF), signed((p >> 4) & 0xF)


def _unpack_int4(packed):
    """(N, K//2) uint8 -> (N, K) f32 in [-7, 7]."""
    lo, hi = _nibbles(packed)
    return jnp.stack([lo, hi], axis=-1).reshape(packed.shape[0], -1)


def _quant_matmul_kernel(*refs, bits, nk):
    """One (M, bn) output tile: the k-axis is the sequential grid
    dimension; each step dequantizes ONE weight tile in VMEM and
    accumulates x_tile @ w_tile.T in f32 scratch — the f32 weight tile
    exists only on-chip, never in HBM.  int4 takes x pre-split into its
    even and odd k columns and multiplies each against its own nibble
    plane: interleaving the nibbles back into k order inside the kernel
    is a lane shuffle Mosaic does not lower."""
    *x_refs, qw_ref, sc_ref, o_ref, acc_ref = refs
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if bits == 4:
        planes = _nibbles(qw_ref[:])                 # 2 x (bn, bk//2)
    else:
        planes = (qw_ref[:].astype(jnp.float32),)    # (bn, bk)
    for x_ref, w in zip(x_refs, planes):
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            x_ref[:].astype(jnp.float32), w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (M, bn)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[:] = (acc_ref[:] * sc_ref[:]).astype(o_ref.dtype)


def _quant_matmul_xla(x, qw, scales, bits):
    if bits == 4:
        w = _unpack_int4(qw)
    else:
        w = qw.astype(jnp.float32)
    w = w * scales[:, None]
    return jax.lax.dot_general(
        x.astype(jnp.float32), w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


def quant_matmul(x: jax.Array, qw: jax.Array, scales: jax.Array,
                 bits: int = 8, block_n: int = 256, block_k: int = 512,
                 use_pallas=None) -> jax.Array:
    """``x @ dequant(qw).T`` with per-channel scales (see
    :func:`quantize_weight`).  ``x``: (..., K); returns (..., N).

    ``use_pallas``: None consults ``MXNET_TPU_PALLAS_QUANT`` (``1`` /
    ``0``; default: pallas on TPU, XLA elsewhere — the XLA form is what
    GSPMD shards for tensor-parallel serving)."""
    if use_pallas is None:
        knob = os.environ.get("MXNET_TPU_PALLAS_QUANT", "")
        if knob in ("0", "1"):
            use_pallas = knob == "1"
        else:
            use_pallas = not _interpret(x, qw)
    N = qw.shape[0]
    K = x.shape[-1]
    if not use_pallas:
        return _quant_matmul_xla(x, qw, scales, bits)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bn = min(block_n, N)
    while N % bn:
        bn //= 2
    if bits == 4:
        # two k per packed byte: x rides as its even / odd k columns
        if K % 2:
            x2 = jnp.pad(x2, ((0, 0), (0, 1)))
        xs = (x2[:, 0::2], x2[:, 1::2])
    else:
        xs = (x2,)
    kc = qw.shape[1]               # columns of each x operand and of qw
    bk = min(block_k // len(xs), kc)
    while kc % bk:
        bk //= 2
    nk = kc // bk
    kern = functools.partial(_quant_matmul_kernel, bits=bits, nk=nk)
    sc2 = scales.reshape(1, N)     # 2-D: a (bn,) block has no (8,128) tile
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern,
            grid=(N // bn, nk),
            in_specs=[pl.BlockSpec((M, bk), lambda n, k_: (0, k_))
                      for _ in xs] + [
                pl.BlockSpec((bn, bk), lambda n, k_: (n, k_)),
                pl.BlockSpec((1, bn), lambda n, k_: (0, n)),
            ],
            out_specs=pl.BlockSpec((M, bn), lambda n, k_: (0, n)),
            out_shape=_out_struct((M, N), x.dtype, x2, qw, sc2),
            scratch_shapes=[pltpu.VMEM((M, bn), jnp.float32)],
            interpret=_interpret(x, qw), name="quant_matmul",
        )(*xs, qw, sc2)
    return out.reshape(lead + (N,))
