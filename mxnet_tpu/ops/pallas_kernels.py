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

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["two_bit_compress", "fused_attention", "fused_attention_fwd",
           "fused_attention_bwd", "decode_attention", "quantize_weight",
           "quant_matmul"]


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

# lse/delta residuals carry a broadcast 128-lane trailing dim — the same
# layout jax's own TPU flash kernel uses (MIN_BLOCK_SIZE lanes): Mosaic
# wants the last dim on the 128-lane register file, and the ×128 HBM
# cost is O(T·128) — noise next to the O(T²) scores the kernel exists to
# avoid materializing.
_LSE_LANES = 128


def _fit_block(block, T, dtype):
    """Largest halving of ``block`` that divides ``T`` and stays on the
    dtype's sublane tile (8 rows of 32 bits: 8 for f32, 16 for bf16);
    a length no such block divides is taken whole, which Mosaic accepts
    as "equal to the array dimension"."""
    tile = 8 * 4 // jnp.dtype(dtype).itemsize
    b = min(block, T)
    while T % b and b > tile:
        b //= 2
    return b if T % b == 0 and b % tile == 0 else T


def _pick_blocks(block_q, block_k, Tq, Tk, D, dtype, kind):
    """Resolve (block_q, block_k): explicit argument wins, then the
    autotune cache (ops/autotune.py), then the static default — and
    either way fit them to the sequence lengths (:func:`_fit_block`)."""
    if block_q is None or block_k is None:
        from . import autotune as _autotune
        tq, tk = _autotune.flash_blocks(kind, Tq, Tk, D, dtype)
        block_q = block_q or tq
        block_k = block_k or tk
    return _fit_block(block_q, Tq, dtype), _fit_block(block_k, Tk, dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal,
                  block_q, block_k, nk, with_lse):
    """Flash attention cell: one (block_q, D) query block against one
    (block_k, D) K/V block, with the running (max, sum, acc) online-
    softmax state in VMEM scratch.  The k-axis is the innermost grid
    dimension, which TPU executes sequentially — the scratch carries
    across k steps and the output is finalized on the last one."""
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

    # causal: skip k blocks entirely above this q block's last row
    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[:].astype(jnp.float32)           # (bq, D)
        k = k_ref[:].astype(jnp.float32)           # (bk, D)
        v = v_ref[:].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (bq, bk)
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_idx = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_idx >= k_idx, s, jnp.float32(_NEG_BIG))
        m_prev = m_ref[:, 0:1]                     # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[:] = (acc_ref[:] / l_ref[:, 0:1]).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp of the SCALED logits: the backward's whole
            # softmax state in one (bq,) row vector (lane-broadcast)
            lse_ref[:] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], jnp.float32(1e-37)))


def _flash_call(qf, kf, vf, dtype, *, scale, causal, bq, bk, with_lse,
                interpret):
    BH, Tq, D = qf.shape
    Tk = kf.shape[1]
    nk = Tk // bk
    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk, nk=nk,
                             with_lse=with_lse)
    out_shape = [_out_struct((BH, Tq, D), dtype, qf, kf, vf)]
    out_specs = [pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0))]
    if with_lse:
        out_shape.append(
            _out_struct((BH, Tq, _LSE_LANES), jnp.float32, qf, kf, vf))
        out_specs.append(
            pl.BlockSpec((None, bq, _LSE_LANES), lambda b, i, j: (b, i, 0)))
    # this package runs with jax_enable_x64 on (mxnet int64 parity); grid
    # index maps would then trace their literals as i64, which Mosaic
    # cannot legalize — trace the kernel in an x64-off scope
    with jax.enable_x64(False):
        res = pl.pallas_call(
            kern,
            grid=(BH, Tq // bq, nk),
            in_specs=[
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=tuple(out_specs) if with_lse else out_specs[0],
            out_shape=tuple(out_shape) if with_lse else out_shape[0],
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),     # acc
                pltpu.VMEM((bq, 128), jnp.float32),   # running max (lanes
                pltpu.VMEM((bq, 128), jnp.float32),   # + sum, broadcast)
            ],
            interpret=interpret, name="flash_fwd",
        )(qf, kf, vf)
    return res if with_lse else (res, None)


def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale=None,
                    block_q=None, block_k=None) -> jax.Array:
    """Flash attention forward: K/V-blocked online softmax.

    q/k/v: (B, T, H, D) (the parallel/ring.py layout).  Returns
    (B, T, H, D).  Per grid cell only (block_q + 2*block_k, D) tiles and
    a (block_q, block_k) score tile live in VMEM — HBM traffic is
    O(T*D) and the sequence length is bounded by HBM, not VMEM (the
    round-3 kernel held ALL of K/V in VMEM and topped out near T=8k;
    this one runs T=32k+ single-chip, tools/bench_pallas.py).

    ``block_q``/``block_k`` default to the autotune cache
    (ops/autotune.py; MXNET_TPU_AUTOTUNE knobs) falling back to 128/512.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    bq, bk = _pick_blocks(block_q, block_k, Tq, Tk, D, q.dtype, "fwd")
    # (B*H, T, D) lanes-last layout for the MXU
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    out, _ = _flash_call(qf, kf, vf, q.dtype, scale=scale, causal=causal,
                         bq=bq, bk=bk, with_lse=False,
                         interpret=_interpret(q, k, v))
    return out.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)


def fused_attention_fwd(q, k, v, causal=False, scale=None,
                        block_q=None, block_k=None):
    """Forward for the custom vjp: returns ``(out, lse)`` where ``lse``
    is the per-row logsumexp of the scaled logits, shape
    ``(B*H, Tq, 128)`` f32 (lane-broadcast — see ``_LSE_LANES``).  With
    this residual the backward never rematerializes the softmax
    normalizer: one extra O(T) output instead of re-running the O(T²)
    forward."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    bq, bk = _pick_blocks(block_q, block_k, Tq, Tk, D, q.dtype, "fwd")
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    out, lse = _flash_call(qf, kf, vf, q.dtype, scale=scale, causal=causal,
                           bq=bq, bk=bk, with_lse=True,
                           interpret=_interpret(q, k, v))
    return out.reshape(B, H, Tq, D).transpose(0, 2, 1, 3), lse


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                         dq_ref, acc_ref, *, scale, causal, block_q,
                         block_k, nk):
    """dQ cell: one (bq, D) query block against the sequential k-axis.
    Recompute-free online-softmax backward: p rebuilds from the saved
    row logsumexp (one exp per score — never the O(T²) softmax), and
    ``delta = rowsum(dO·O)`` folds the dV-normalizer term."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[:].astype(jnp.float32)            # (bq, D)
        k = k_ref[:].astype(jnp.float32)            # (bk, D)
        v = v_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)          # (bq, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bq, bk)
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_idx = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_idx >= k_idx, s, jnp.float32(_NEG_BIG))
        p = jnp.exp(s - lse_ref[:, 0:1])            # masked rows -> 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        ds = p * (dp - dl_ref[:, 0:1]) * scale
        acc_ref[:] = acc_ref[:] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                          causal, block_q, block_k, nq):
    """dK/dV cell: one (bk, D) key/value block against the sequential
    q-axis, accumulating both grads in VMEM scratch."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # causal: q blocks entirely ABOVE this k block see none of it
    live = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[:].astype(jnp.float32)            # (bq, D)
        k = k_ref[:].astype(jnp.float32)            # (bk, D)
        v = v_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)          # (bq, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bq, bk)
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_idx = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_idx >= k_idx, s, jnp.float32(_NEG_BIG))
        p = jnp.exp(s - lse_ref[:, 0:1])            # (bq, bk)
        # dV += P^T dO
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        ds = p * (dp - dl_ref[:, 0:1]) * scale
        # dK += dS^T Q
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def fused_attention_bwd(q, k, v, out, lse, do, causal=False, scale=None,
                        block_q=None, block_k=None):
    """Flash attention backward: K/V-blocked dQ/dK/dV from the saved
    logsumexp residual — no forward recomputation, no (T, T) tensor in
    HBM (the einsum-vjp fallback materializes the full probability
    matrix AND its gradient: ~2·B·H·T² values of HBM traffic per layer
    that this kernel never touches).

    q/k/v/out/do: (B, T, H, D); ``lse``: (B*H, Tq, 128) f32 from
    :func:`fused_attention_fwd`.  Returns (dq, dk, dv) in the input
    dtypes.  Two pallas calls: dQ accumulates over the sequential
    k-axis, dK/dV over the sequential q-axis.  Block sizes default to
    the autotune cache ("bwd" entry) falling back to 128/128."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    bq, bk = _pick_blocks(block_q, block_k, Tq, Tk, D, q.dtype, "bwd")
    nq, nk = Tq // bq, Tk // bk
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    dof = do.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    outf = out.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    # delta = rowsum(dO · O): one cheap fused O(T·D) pass in XLA, then
    # lane-broadcast like lse so both ride the same (bq, 128) blocks
    delta = jnp.sum(dof.astype(jnp.float32) * outf.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (B * H, Tq, _LSE_LANES))
    interpret = _interpret(q, k, v)
    operands = (qf, kf, vf, dof, lse, delta)
    with jax.enable_x64(False):
        dq = pl.pallas_call(
            functools.partial(_flash_bwd_dq_kernel, scale=scale,
                              causal=causal, block_q=bq, block_k=bk,
                              nk=nk),
            grid=(B * H, nq, nk),
            in_specs=[
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bq, _LSE_LANES),
                             lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bq, _LSE_LANES),
                             lambda b, i, j: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            out_shape=_out_struct((B * H, Tq, D), q.dtype, *operands),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interpret, name="flash_bwd_dq",
        )(*operands)
        dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_dkv_kernel, scale=scale,
                              causal=causal, block_q=bq, block_k=bk,
                              nq=nq),
            grid=(B * H, nk, nq),
            in_specs=[
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, bq, _LSE_LANES),
                             lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, bq, _LSE_LANES),
                             lambda b, i, j: (b, j, 0)),
            ],
            out_specs=(
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, i, 0)),
            ),
            out_shape=(_out_struct((B * H, Tk, D), k.dtype, *operands),
                       _out_struct((B * H, Tk, D), v.dtype, *operands)),
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            interpret=interpret, name="flash_bwd_dkv",
        )(*operands)

    def unflat(x, T):
        return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)

    return unflat(dq, Tq), unflat(dk, Tk), unflat(dv, Tk)


# ---------------------------------------------------------------------------
# paged single-query decode attention
# ---------------------------------------------------------------------------
#
# The serving decode path (mxnet_tpu/serving/decode.py) holds K/V in a
# fixed PAGE POOL of shape (P, H, page, D): physical pages handed out by
# a host-side allocator, one logical sequence = a per-slot row of page
# ids.  Decode attention is then ONE query token per slot against that
# pool.  The Pallas kernel walks a sequence's pages directly via
# scalar-prefetched page-table indices (the PR-14 PrefetchScalarGridSpec
# technique): grid (slot, logical_page), each step DMAs exactly one
# (H, page, D) physical page — the pool never materializes per-sequence,
# so HBM traffic is O(tokens_cached · D), not O(slots · max_seq · D).
# The online-softmax state (running max / sum / accumulator) is the same
# logsumexp machinery as the flash kernels above, carried across the
# sequential page axis in VMEM scratch.

def _decode_attn_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *, page, n_pages, scale):
    """One (slot, logical page) cell.  Every value keeps the
    (H, page|1, D|1) rank of the page block: a one-token query against a
    page is a matrix-VECTOR product per head, which Mosaic's matmul does
    not take (it refused the batched ``(H,page,D)·(H,D)`` dot_general:
    "failed to parse TPU_DotDimensionNumbersAttr"), so the scores and the
    weighted sum are broadcast-multiplies reduced over lanes / sublanes
    on the VPU — decode attention is bandwidth-bound either way."""
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, jnp.float32(_NEG_BIG))
        l_ref[:] = jnp.zeros_like(l_ref)

    # a page with no valid token (beyond this slot's cached length) is
    # skipped entirely — the DMA still happened (the index map runs for
    # every grid cell; unused table entries point at the trash page) but
    # no FLOPs or state updates are spent on it
    live = j * page < len_ref[s]

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # (H, 1, D)
        k = k_ref[0].astype(jnp.float32)            # (H, page, D)
        v = v_ref[0].astype(jnp.float32)
        s_hp = jnp.sum(k * q, axis=-1, keepdims=True) * scale  # (H, page, 1)
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s_hp.shape, 1)
        s_hp = jnp.where(pos < len_ref[s], s_hp, jnp.float32(_NEG_BIG))
        m_prev = m_ref[:]                           # (H, 1, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s_hp, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_hp - m_new)                   # (H, page, 1)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * corr + jnp.sum(p * v, axis=1,
                                                 keepdims=True)  # (H, 1, D)

    @pl.when(j == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], jnp.float32(1e-37))
                    ).astype(o_ref.dtype)


def _decode_attn_pallas(q, k_pages, v_pages, page_table, seq_lens, scale,
                        interpret):
    S, H, D = q.shape
    P, _, page, _ = k_pages.shape
    n_pages = page_table.shape[1]
    kern = functools.partial(_decode_attn_kernel, page=page,
                             n_pages=n_pages, scale=scale)
    # q and the output ride as (S, H, 1, D): the block's trailing two dims
    # then equal the array's, and the kernel never reshapes
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, 1, D), lambda s, j, pt, ln: (s, 0, 0, 0)),
            pl.BlockSpec((1, H, page, D),
                         lambda s, j, pt, ln: (pt[s, j], 0, 0, 0)),
            pl.BlockSpec((1, H, page, D),
                         lambda s, j, pt, ln: (pt[s, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, 1, D),
                               lambda s, j, pt, ln: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1, D), jnp.float32),     # acc
            pltpu.VMEM((H, 1, 1), jnp.float32),     # running max
            pltpu.VMEM((H, 1, 1), jnp.float32),     # running sum
        ],
    )
    q4 = q.reshape(S, H, 1, D)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=_out_struct((S, H, 1, D), q.dtype, q4, k_pages,
                                  v_pages),
            interpret=interpret, name="decode_attn",
        )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
          q4, k_pages, v_pages)
    return out.reshape(S, H, D)


def _decode_attn_xla(q, k_pages, v_pages, page_table, seq_lens, scale):
    """XLA formulation: gather the slots' pages, mask, one softmax.  It
    materializes (S, max_pages·page, H·D) per call — fine on CPU and the
    form GSPMD can shard over a tp axis (pallas_call is a partitioning
    black box; the tp serving export always uses this path)."""
    S, H, D = q.shape
    page = k_pages.shape[2]
    n_pages = page_table.shape[1]
    T = n_pages * page
    # (S, n_pages, H, page, D) -> (S, H, T, D)
    k = k_pages[page_table].transpose(0, 2, 1, 3, 4).reshape(S, H, T, D)
    v = v_pages[page_table].transpose(0, 2, 1, 3, 4).reshape(S, H, T, D)
    s_sht = jnp.einsum("shd,shtd->sht", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
    pos = jnp.arange(T, dtype=jnp.int32)[None, None, :]
    s_sht = jnp.where(pos < seq_lens[:, None, None].astype(jnp.int32),
                      s_sht, jnp.float32(_NEG_BIG))
    p = jax.nn.softmax(s_sht, axis=-1)
    out = jnp.einsum("sht,shtd->shd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


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

    ``use_pallas``: None consults ``MXNET_TPU_PALLAS_DECODE``
    (``1``/``0``/``auto``; auto = the ops/autotune cache's measured
    winner, falling back to pallas on TPU and XLA elsewhere)."""
    S, H, D = q.shape
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    if use_pallas is None:
        knob = os.environ.get("MXNET_TPU_PALLAS_DECODE", "auto")
        if knob in ("0", "1"):
            use_pallas = knob == "1"
        else:
            from . import autotune as _autotune
            use_pallas = _autotune.decode_backend(
                S, H, D, k_pages.shape[2], str(q.dtype)) == "pallas"
    if not use_pallas:
        return _decode_attn_xla(q, k_pages, v_pages, page_table, seq_lens,
                                float(scale))
    return _decode_attn_pallas(q, k_pages, v_pages, page_table, seq_lens,
                               float(scale),
                               _interpret(q, k_pages, v_pages))


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
