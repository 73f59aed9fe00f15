"""Neural-network ops: the reference's src/operator/nn/ + legacy root ops.

Reference: fully_connected-inl.h, convolution-inl.h (+nn/cudnn/ wrappers),
pooling-inl.h, batch_norm-inl.h, dropout-inl.h, activation-inl.h,
leaky_relu-inl.h, softmax_output-inl.h, lrn-inl.h, upsampling-inl.h.

TPU mapping: convolutions/matmuls become single lax ops XLA tiles onto the
MXU (no cuDNN algo registry needed — that entire autotuning subsystem,
cudnn_algoreg-inl.h, is subsumed by XLA); BatchNorm/Dropout/activations are
HBM-bandwidth ops XLA fuses into neighbours.  Data layout stays NCHW at the
API (reference default) — XLA repacks internally for the hardware.

Loss-head ops (SoftmaxOutput, *RegressionOutput, MakeLoss) reproduce the
reference's defining quirk: their backward IGNORES the incoming gradient and
emits the loss gradient directly (softmax_output-inl.h backward writes
out - one_hot(label)).  Autodiff cannot derive that from the forward, so they
are jax.custom_vjp primitives.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..base import (MXNetError, attr_bool, attr_dtype, attr_float, attr_int,
                    attr_shape, attr_str, Param)
from .registry import register, get_op


# ---------------------------------------------------------------------------
# FullyConnected
# ---------------------------------------------------------------------------

def _fc_inputs(attrs, num_args=None):
    if attrs is not None and not attrs.get("no_bias", False):
        return ["data", "weight", "bias"]
    return ["data", "weight"]


@register("FullyConnected", inputs=_fc_inputs,
          params=dict(num_hidden=attr_int(required=True),
                      no_bias=attr_bool(False), flatten=attr_bool(True)))
def _fully_connected(attrs, data, weight, bias=None):
    if attrs.flatten:
        x = data.reshape(data.shape[0], -1)
    else:
        x = data
    out = jax.lax.dot_general(
        x, weight, (((x.ndim - 1,), (1,)), ((), ())))
    if bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------

def _conv_inputs(attrs, num_args=None):
    if attrs is not None and not attrs.get("no_bias", False):
        return ["data", "weight", "bias"]
    return ["data", "weight"]


_CONV_PARAMS = dict(
    kernel=attr_shape(required=True), stride=attr_shape(()),
    dilate=attr_shape(()), pad=attr_shape(()),
    num_filter=attr_int(required=True), num_group=attr_int(1),
    workspace=attr_int(1024), no_bias=attr_bool(False),
    cudnn_tune=attr_str(None), cudnn_off=attr_bool(False),
    layout=attr_str(None))


def _conv_nd(attrs, x):
    nd = len(attrs.kernel)
    stride = attrs.stride or (1,) * nd
    dilate = attrs.dilate or (1,) * nd
    pad = attrs.pad or (0,) * nd
    return nd, stride, dilate, [(p, p) for p in pad]


@register("Convolution", inputs=_conv_inputs, params=dict(_CONV_PARAMS),
          aliases=("Convolution_v1",))
def _convolution(attrs, x, w, bias=None):
    """NC(D)HW activations, OIHW weights (reference convolution-inl.h).

    layout="NHWC" (2-d only) runs channels-last end to end with OHWI
    weights — the TPU-native layout path (conv feature dim falls on the
    lane dimension without relayout; see PERF.md r5)."""
    nd, stride, dilate, pad = _conv_nd(attrs, x)
    if attrs.layout == "NHWC":
        assert nd == 2, "NHWC layout is 2-d only"
        dn = jax.lax.conv_dimension_numbers(
            x.shape, w.shape, ("NHWC", "OHWI", "NHWC"))
        out = jax.lax.conv_general_dilated(
            x, w, window_strides=stride, padding=pad, rhs_dilation=dilate,
            dimension_numbers=dn, feature_group_count=attrs.num_group)
        if bias is not None:
            out = out + bias
        return out
    spatial = "DHW"[-nd:]
    dn = jax.lax.conv_dimension_numbers(
        x.shape, w.shape,
        ("NC" + spatial, "OI" + spatial, "NC" + spatial))
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=pad, rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=attrs.num_group)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@register("Deconvolution", inputs=_conv_inputs,
          params=dict(_CONV_PARAMS, adj=attr_shape(()),
                      target_shape=attr_shape(())))
def _deconvolution(attrs, x, w, bias=None):
    """Transposed conv (reference deconvolution-inl.h); weights IOHW like
    the reference shares with Convolution ((C_in, C_out/g, kH, kW))."""
    nd, stride, dilate, pad = _conv_nd(attrs, x)
    spatial = "DHW"[-nd:]
    g = attrs.num_group
    if g > 1:
        # XLA grouped conv wants rhs (C_in/g, g*C_out/g, ...): regroup the
        # reference's (C_in, C_out/g, ...) block layout along the O dim
        cin = w.shape[0]
        w = w.reshape((g, cin // g) + w.shape[1:]) \
            .transpose((1, 0, 2) + tuple(range(3, 3 + nd))) \
            .reshape((cin // g, g * w.shape[1]) + w.shape[2:])
    dn = jax.lax.conv_dimension_numbers(
        x.shape, w.shape, ("NC" + spatial, "IO" + spatial, "NC" + spatial))
    adj = attrs.adj or (0,) * nd
    # conv_transpose padding: reference computes output = (i-1)*s - 2p + k + adj
    pad_t = [(attrs.kernel[i] - 1 - pad[i][0],
              attrs.kernel[i] - 1 - pad[i][1] + adj[i]) for i in range(nd)]
    # transposed conv = dilated-input conv with the spatially flipped kernel
    out = jax.lax.conv_general_dilated(
        x, jnp.flip(w, axis=tuple(range(2, 2 + nd))), window_strides=(1,) * nd,
        padding=pad_t, lhs_dilation=stride, rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=attrs.num_group)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

@register("Pooling", inputs=("data",),
          params=dict(kernel=attr_shape(()), pool_type=attr_str("max"),
                      global_pool=attr_bool(False), cudnn_off=attr_bool(False),
                      pooling_convention=attr_str("valid"),
                      stride=attr_shape(()), pad=attr_shape(()),
                      layout=attr_str(None)),
          aliases=("Pooling_v1",))
def _pooling(attrs, x):
    nd = x.ndim - 2
    nhwc = attrs.layout == "NHWC"
    sp0 = 1 if nhwc else 2          # first spatial axis
    if attrs.global_pool:
        kernel = x.shape[sp0:sp0 + nd]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = attrs.kernel
        stride = attrs.stride or (1,) * nd
        pad = attrs.pad or (0,) * nd
    if nhwc:
        window = (1,) + tuple(kernel) + (1,)
        strides = (1,) + tuple(stride) + (1,)
        pads = ((0, 0),) + tuple((p, p) for p in pad) + ((0, 0),)
    else:
        window = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
        pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if attrs.pooling_convention == "full" and not attrs.global_pool:
        # ceil-mode output: extend right/bottom padding so ceil division holds
        pads = list(pads)
        for i in range(nd):
            in_sz = x.shape[sp0 + i] + 2 * pad[i]
            out_sz = -(-(in_sz - kernel[i]) // stride[i]) + 1
            need = (out_sz - 1) * stride[i] + kernel[i] - in_sz
            pads[sp0 + i] = (pad[i], pad[i] + max(0, need))
        pads = tuple(pads)
    if attrs.pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, strides, pads)
    ssum = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
    if attrs.pool_type == "sum":
        return ssum
    # avg: reference divides by kernel size (count_include_pad=True default)
    return ssum / float(np.prod(kernel))


@register("UpSampling", variadic=True,
          params=dict(num_args=attr_int(1), scale=attr_int(required=True),
                      sample_type=attr_str("nearest"), num_filter=attr_int(0),
                      multi_input_mode=attr_str("concat"),
                      workspace=attr_int(512)))
def _upsampling(attrs, *xs):
    """reference: src/operator/upsampling-inl.h (nearest mode)."""
    s = attrs.scale
    outs = []
    for x in xs:
        out = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
        outs.append(out)
    if len(outs) == 1:
        return outs[0]
    if attrs.multi_input_mode == "sum":
        return sum(outs)
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

@register("Activation", inputs=("data",),
          params=dict(act_type=attr_str(required=True)))
def _activation(attrs, x):
    return {
        "relu": lambda v: jnp.maximum(v, 0),
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
        # TPU-era extension (later-reference LeakyReLU gelu mode);
        # exact erf formulation, matching the reference GELU
        "gelu": lambda v: jax.nn.gelu(v, approximate=False),
        "silu": jax.nn.silu,
    }[attrs.act_type](x)


def _lrelu_inputs(attrs, num_args=None):
    if attrs is not None and attrs.get("act_type", "leaky") == "prelu":
        return ["data", "gamma"]
    return ["data"]


@register("LeakyReLU", inputs=_lrelu_inputs,
          params=dict(act_type=attr_str("leaky"), slope=attr_float(0.25),
                      lower_bound=attr_float(0.125), upper_bound=attr_float(0.334)),
          needs_rng=True, mode_dependent=True)
def _leaky_relu(attrs, key, x, gamma=None):
    t = attrs.act_type
    if t == "leaky":
        return jnp.where(x >= 0, x, attrs.slope * x)
    if t == "elu":
        return jnp.where(x >= 0, x, attrs.slope * jnp.expm1(x))
    if t == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else gamma
        return jnp.where(x >= 0, x, g * x)
    if t == "rrelu":
        if attrs.get("_train", False):
            slope = jax.random.uniform(
                key, x.shape, x.dtype, attrs.lower_bound, attrs.upper_bound)
        else:
            slope = (attrs.lower_bound + attrs.upper_bound) / 2.0
        return jnp.where(x >= 0, x, slope * x)
    if t == "gelu":
        # the later-reference spelling LeakyReLU(act_type='gelu'); exact erf
        return jax.nn.gelu(x, approximate=False)
    raise ValueError("unknown act_type %s" % t)


@register("softmax", inputs=("data",),
          params=dict(axis=Param(int, -1), temperature=attr_float(None)))
def _softmax(attrs, x):
    if attrs.temperature is not None:
        x = x / attrs.temperature
    return jax.nn.softmax(x, axis=attrs.axis)


@register("log_softmax", inputs=("data",),
          params=dict(axis=Param(int, -1), temperature=attr_float(None)))
def _log_softmax(attrs, x):
    if attrs.temperature is not None:
        x = x / attrs.temperature
    return jax.nn.log_softmax(x, axis=attrs.axis)


@register("SoftmaxActivation", inputs=("data",),
          params=dict(mode=attr_str("instance")))
def _softmax_activation(attrs, x):
    if attrs.mode == "channel":
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


# ---------------------------------------------------------------------------
# BatchNorm — with functional writeback of moving stats.
# Inputs:  data, gamma, beta, moving_mean, moving_var
# Outputs: out, saved_mean, saved_var, new_moving_mean, new_moving_var
# (first 3 visible — matches reference output_mean_var; last 2 written back
#  into the aux NDArrays by the runtime, replacing in-place mutation).
# ---------------------------------------------------------------------------

@register("BatchNorm",
          inputs=("data", "gamma", "beta", "moving_mean", "moving_var"),
          params=dict(eps=attr_float(1e-3), momentum=attr_float(0.9),
                      fix_gamma=attr_bool(True), use_global_stats=attr_bool(False),
                      output_mean_var=attr_bool(False), axis=attr_int(1),
                      cudnn_off=attr_bool(False)),
          num_outputs=5, num_visible_outputs=1,
          writeback={3: 3, 4: 4}, aux_inputs=(3, 4), mode_dependent=True,
          aliases=("BatchNorm_v1",))
def _batch_norm(attrs, x, gamma, beta, mov_mean, mov_var):
    ax = attrs.axis % x.ndim
    red = tuple(i for i in range(x.ndim) if i != ax)
    bshape = tuple(x.shape[ax] if i == ax else 1 for i in range(x.ndim))
    train = attrs.get("_train", False) and not attrs.use_global_stats
    xf = x.astype(jnp.float32)
    if train:
        mean = jnp.mean(xf, axis=red)
        var = jnp.var(xf, axis=red)
        m = attrs.momentum
        new_mm = mov_mean * m + mean * (1 - m)
        new_mv = mov_var * m + var * (1 - m)
    else:
        mean, var = mov_mean, mov_var
        new_mm, new_mv = mov_mean, mov_var
    g = jnp.ones_like(gamma) if attrs.fix_gamma else gamma
    inv = jax.lax.rsqrt(var + attrs.eps)
    out = (xf - mean.reshape(bshape)) * (inv * g).reshape(bshape) \
        + beta.reshape(bshape)
    return (out.astype(x.dtype), mean, var, new_mm, new_mv)


@register("InstanceNorm", inputs=("data", "gamma", "beta"),
          params=dict(eps=attr_float(1e-3)))
def _instance_norm(attrs, x, gamma, beta):
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    return (x - mean) * jax.lax.rsqrt(var + attrs.eps) * \
        gamma.reshape(bshape) + beta.reshape(bshape)


@register("LayerNorm", inputs=("data", "gamma", "beta"),
          params=dict(axis=Param(int, -1), eps=attr_float(1e-5),
                      output_mean_var=attr_bool(False)),
          num_outputs=3, num_visible_outputs=1)
def _layer_norm(attrs, x, gamma, beta):
    # statistics in f32, result back in the input dtype: with bf16
    # activations and f32 affine params (the trainer keeps gamma/beta
    # f32), returning the promoted dtype would silently upcast every
    # downstream matmul to f32 — measured 2x step time on the
    # transformer bench (PERF.md r5)
    ax = attrs.axis
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=ax, keepdims=True)
    var = jnp.var(x32, axis=ax, keepdims=True)
    inv = jax.lax.rsqrt(var + attrs.eps)
    shape = [1] * x.ndim
    shape[ax] = x.shape[ax]
    out = (x32 - mean) * inv * gamma.reshape(shape) + beta.reshape(shape)
    return (out.astype(x.dtype), jnp.squeeze(mean.astype(x.dtype), ax),
            jnp.squeeze(var.astype(x.dtype), ax))


@register("RMSNorm", inputs=("data", "gamma"),
          params=dict(axis=Param(int, -1), eps=attr_float(1e-5)))
def _rms_norm(attrs, x, gamma):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over ``axis``: statistics in
    float32, the result back in the input's dtype (as LayerNorm above: the
    trainer keeps ``*_gamma`` float32)."""
    ax = attrs.axis
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=ax, keepdims=True)
                        + attrs.eps)
    shape = [1] * x.ndim
    shape[ax] = x.shape[ax]
    return (x32 * inv * gamma.reshape(shape)).astype(x.dtype)


@register("_contrib_rotary_embedding", inputs=("data",),
          params=dict(theta=attr_float(10000.0)),
          aliases=("rotary_embedding",))
def _rotary_embedding(attrs, x):
    """Rotary positions over (B, T, H, D), positions 0..T-1, the pairs
    (i, i + D/2) turned by ``pos * theta^(-2i/D)``: angles and the turn in
    float32, the result in the input's dtype."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    freq = jnp.float32(attrs.theta) ** (
        -jnp.arange(half, dtype=jnp.float32) / jnp.float32(half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


@register("LRN", inputs=("data",),
          params=dict(alpha=attr_float(1e-4), beta=attr_float(0.75),
                      knorm=attr_float(2.0), nsize=attr_int(required=True)))
def _lrn(attrs, x):
    """Local response norm across channels (reference lrn-inl.h)."""
    sq = x * x
    n = attrs.nsize
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (x.ndim - 2))
    window = (1, n) + (1,) * (x.ndim - 2)
    ssum = jax.lax.reduce_window(pad, 0.0, jax.lax.add, window,
                                 (1,) * x.ndim, [(0, 0)] * x.ndim)
    return x * jnp.power(attrs.knorm + attrs.alpha / n * ssum, -attrs.beta)


# ---------------------------------------------------------------------------
# Dropout
# ---------------------------------------------------------------------------

@register("Dropout", inputs=("data",),
          params=dict(p=attr_float(0.5), mode=attr_str("training"),
                      axes=attr_shape(())),
          needs_rng=True, mode_dependent=True,
          num_outputs=2, num_visible_outputs=1)
def _dropout(attrs, key, x):
    train = attrs.get("_train", False) or attrs.mode == "always"
    if not train or attrs.p <= 0:
        return x, jnp.ones_like(x)
    shape = list(x.shape)
    for ax in (attrs.axes or ()):
        shape[ax] = 1
    keep = 1.0 - attrs.p
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(x.dtype) / keep
    return x * mask, jnp.broadcast_to(mask, x.shape)


# ---------------------------------------------------------------------------
# Loss heads — custom VJPs reproducing reference backward semantics
# ---------------------------------------------------------------------------

def _normalizer(norm, label_shape, valid):
    if norm == "batch":
        return float(np.prod(label_shape[:1]))
    if norm == "valid":
        return valid
    return 1.0


@register("SoftmaxOutput", inputs=("data", "label"),
          params=dict(grad_scale=attr_float(1.0), ignore_label=attr_float(-1.0),
                      multi_output=attr_bool(False), use_ignore=attr_bool(False),
                      preserve_shape=attr_bool(False),
                      normalization=attr_str("null"),
                      out_grad=attr_bool(False), smooth_alpha=attr_float(0.0)),
          aliases=("Softmax",))
def _softmax_output(attrs, data, label):
    """Forward = softmax(data); backward(data) = (softmax - one_hot(label)) *
    grad_scale / normalizer, ignoring the incoming cotangent — the exact
    semantics of softmax_output-inl.h."""

    multi = attrs.multi_output and data.ndim > 2

    @jax.custom_vjp
    def _f(d, l):
        return _fwd_only(d)

    def _fwd_only(d):
        if multi:
            return jax.nn.softmax(d, axis=1)
        if attrs.preserve_shape:
            return jax.nn.softmax(d, axis=-1)
        return jax.nn.softmax(d.reshape(d.shape[0], -1), axis=-1).reshape(d.shape)

    def _fwd(d, l):
        return _fwd_only(d), (d, l)

    def _bwd(res, g):
        d, l = res
        prob = _fwd_only(d)
        if multi:
            # label (N, spatial...), prob (N, C, spatial...)
            li = l.astype(jnp.int32)
            oh = jax.nn.one_hot(li, d.shape[1], dtype=prob.dtype,
                                axis=1)
            grad = prob - oh
            if attrs.use_ignore:
                keep = (l != attrs.ignore_label)
                grad = grad * keep[:, None].astype(grad.dtype)
                valid = jnp.maximum(jnp.sum(keep), 1).astype(grad.dtype)
            else:
                valid = float(np.prod(l.shape))
        else:
            flat = d.reshape(d.shape[0], -1) if not attrs.preserve_shape else d
            probf = prob.reshape(flat.shape)
            li = l.reshape(-1).astype(jnp.int32) if not attrs.preserve_shape \
                else l.astype(jnp.int32)
            nclass = flat.shape[-1]
            oh = jax.nn.one_hot(li, nclass, dtype=probf.dtype)
            if attrs.smooth_alpha:
                a = attrs.smooth_alpha
                oh = oh * (1 - a) + a / (nclass - 1) * (1 - oh)
            if not attrs.preserve_shape:
                oh = oh.reshape(probf.shape)
            grad = probf - oh
            if attrs.use_ignore:
                keep = (li != attrs.ignore_label)
                grad = grad * jnp.expand_dims(keep, -1).astype(grad.dtype)
                valid = jnp.maximum(jnp.sum(keep), 1).astype(grad.dtype)
            else:
                valid = float(np.prod(li.shape))
            grad = grad.reshape(d.shape)
        if attrs.normalization == "batch":
            grad = grad / d.shape[0]
        elif attrs.normalization == "valid":
            grad = grad / valid
        grad = grad * attrs.grad_scale
        if attrs.out_grad:
            grad = grad * g
        return grad.astype(d.dtype), jnp.zeros_like(l)

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


def _make_regression(name, fwd, grad):
    @register(name, inputs=("data", "label"),
              params=dict(grad_scale=attr_float(1.0)))
    def _op(attrs, data, label):
        @jax.custom_vjp
        def _f(d, l):
            return fwd(d)

        def _vfwd(d, l):
            return fwd(d), (d, l)

        def _vbwd(res, g):
            d, l = res
            num = float(np.prod(d.shape) / d.shape[0])
            gd = grad(fwd(d), l.reshape(d.shape)) * attrs.grad_scale / num
            return gd.astype(d.dtype), jnp.zeros_like(l)

        _f.defvjp(_vfwd, _vbwd)
        return _f(data, label)
    return _op


_make_regression("LinearRegressionOutput", lambda d: d, lambda o, l: o - l)
_make_regression("MAERegressionOutput", lambda d: d, lambda o, l: jnp.sign(o - l))
_make_regression("LogisticRegressionOutput", jax.nn.sigmoid, lambda o, l: o - l)


@register("MakeLoss", inputs=("data",),
          params=dict(grad_scale=attr_float(1.0),
                      valid_thresh=attr_float(0.0),
                      normalization=attr_str("null")))
def _make_loss(attrs, data):
    """Forward identity; backward emits grad_scale (reference make_loss)."""

    @jax.custom_vjp
    def _f(d):
        return d

    def _fwd(d):
        return d, d

    def _bwd(d, g):
        scale = attrs.grad_scale
        if attrs.normalization == "batch":
            scale = scale / d.shape[0]
        elif attrs.normalization == "valid":
            valid = jnp.maximum((d > attrs.valid_thresh).sum(), 1)
            scale = scale / valid.astype(d.dtype)
        return (jnp.full_like(d, 1.0) * scale,)

    _f.defvjp(_fwd, _bwd)
    return _f(data)


@register("SVMOutput", inputs=("data", "label"),
          params=dict(margin=attr_float(1.0),
                      regularization_coefficient=attr_float(1.0),
                      use_linear=attr_bool(False)))
def _svm_output(attrs, data, label):
    """reference: src/operator/svm_output-inl.h — forward identity."""

    @jax.custom_vjp
    def _f(d, l):
        return d

    def _fwd(d, l):
        return d, (d, l)

    def _bwd(res, g):
        d, l = res
        li = l.astype(jnp.int32)
        oh = jax.nn.one_hot(li, d.shape[1], dtype=d.dtype)
        score_correct = jnp.take_along_axis(d, li[:, None], axis=1)
        margin_viol = (d - score_correct + attrs.margin) > 0
        c = attrs.regularization_coefficient
        if attrs.use_linear:
            grad = jnp.where(margin_viol, c, 0.0) * (1 - oh)
            grad = grad - oh * grad.sum(axis=1, keepdims=True)
        else:
            slack = jnp.maximum(d - score_correct + attrs.margin, 0) * (1 - oh)
            grad = 2 * c * slack
            grad = grad - oh * grad.sum(axis=1, keepdims=True)
        return grad.astype(d.dtype), jnp.zeros_like(l)

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


@register("CTCLoss", inputs=("data", "label"),
          params=dict(use_data_lengths=attr_bool(False),
                      use_label_lengths=attr_bool(False),
                      blank_label=attr_str("first")),
          aliases=("ctc_loss", "_contrib_CTCLoss", "_contrib_ctc_loss"))
def _ctc_loss(attrs, data, label):
    """CTC loss (reference: src/operator/contrib/ctc_loss-inl.h, warpctc).
    data: (T, N, C) unnormalised activations; label: (N, L) padded with 0
    (blank_label='first') — forward returns per-example loss; gradients flow
    through log_softmax via autodiff (no custom kernel needed on TPU)."""
    T, N, C = data.shape
    logprobs = jax.nn.log_softmax(data, axis=-1)
    blank = 0 if attrs.blank_label == "first" else C - 1
    lab = label.astype(jnp.int32)
    if attrs.blank_label == "first":
        # channel 0 is blank; label VALUES are channel indices (1-based
        # alphabet), 0 marks padding — no shift (shifting by -1 would
        # collide class 1 with the blank channel)
        lab = jnp.where(lab == 0, -1, lab)
    else:
        # 'last': labels are 0-based channel indices, C-1 is blank;
        # negative values mark padding
        lab = jnp.where(lab < 0, -1, lab)
    L = lab.shape[1]
    # extended label sequence with blanks: length 2L+1
    ext = jnp.full((N, 2 * L + 1), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(jnp.where(lab >= 0, lab, blank))
    valid = jnp.where(lab >= 0, 1, 0)
    lab_len = valid.sum(axis=1)
    ext_len = 2 * lab_len + 1
    S = 2 * L + 1
    neg_inf = -1e30

    def step(alpha, logp):
        # alpha: (N, S); logp: (N, C)
        emit = jnp.take_along_axis(logp, ext, axis=1)  # (N, S)
        a0 = alpha
        a1 = jnp.pad(alpha[:, :-1], ((0, 0), (1, 0)), constant_values=neg_inf)
        a2 = jnp.pad(alpha[:, :-2], ((0, 0), (2, 0)), constant_values=neg_inf)
        # a2 allowed only when ext[s] != blank and ext[s] != ext[s-2]
        ext_m2 = jnp.pad(ext[:, :-2], ((0, 0), (2, 0)), constant_values=-2)
        allow2 = (ext != blank) & (ext != ext_m2)
        merged = jnp.logaddexp(a0, a1)
        merged = jnp.where(allow2, jnp.logaddexp(merged, a2), merged)
        return merged + emit, None

    alpha0 = jnp.full((N, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(jnp.take_along_axis(
        logprobs[0], ext[:, 0:1], axis=1)[:, 0])
    alpha0 = alpha0.at[:, 1].set(jnp.where(
        lab_len > 0,
        jnp.take_along_axis(logprobs[0], ext[:, 1:2], axis=1)[:, 0], neg_inf))
    alpha, _ = jax.lax.scan(step, alpha0, logprobs[1:])
    last = jnp.take_along_axis(alpha, (ext_len - 1)[:, None], axis=1)[:, 0]
    # empty (all-padding) label rows have ext_len == 1: there is no
    # "ended on the final symbol" state, and ext_len-2 == -1 would wrap
    last2 = jnp.where(
        lab_len > 0,
        jnp.take_along_axis(alpha,
                            jnp.maximum(ext_len - 2, 0)[:, None],
                            axis=1)[:, 0],
        neg_inf)
    ll = jnp.logaddexp(last, last2)
    return -ll


@register("softmax_cross_entropy", inputs=("data", "label"))
def _softmax_cross_entropy(attrs, data, label):
    """Total softmax CE loss as a length-1 array (reference
    src/operator/loss_binary_op.cc)."""
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32)[:, None], axis=-1)[:, 0]
    return -jnp.sum(picked)[None]


@register("IdentityAttachKLSparseReg", inputs=("data",),
          params=dict(sparseness_target=attr_float(0.1),
                      penalty=attr_float(0.001), momentum=attr_float(0.9)))
def _identity_attach_kl_sparse_reg(attrs, x):
    """Identity forward with a KL-sparseness penalty on the gradient
    (reference src/operator/identity_attach_KL_sparse_reg-inl.h): the
    backward adds penalty * (-rho/rho_hat + (1-rho)/(1-rho_hat)) where
    rho_hat is the batch mean activation (sigmoid-range data assumed).
    Stateless analog: rho_hat comes from the CURRENT batch (the reference
    keeps a momentum-smoothed aux copy for logging; the gradient uses the
    batch value the same way)."""
    rho = attrs.sparseness_target
    penalty = attrs.penalty

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, x

    def bwd(saved, g):
        rho_hat = jnp.clip(jnp.mean(saved, axis=0, keepdims=True),
                           1e-6, 1 - 1e-6)
        reg = penalty * (-rho / rho_hat + (1 - rho) / (1 - rho_hat))
        return (g + reg.astype(g.dtype),)

    f.defvjp(fwd, bwd)
    return f(x)


# ---------------------------------------------------------------------------
# Fused attention (Pallas kernel as a graph op) — beyond-reference: the
# reference predates attention (SURVEY §5.7); this exposes
# ops/pallas_kernels.fused_attention to Symbol/Gluon models.
# ---------------------------------------------------------------------------

# Resolved ONCE at import: the op body runs inside jit traces whose cache
# key does not include the environment, so a post-first-trace change to
# MXNET_FLASH_MIN_SEQ would be silently ignored — freezing it here makes
# that explicit.  Per-call control stays available via the op's
# flash_min_seq attr (which IS part of the jit cache key).
#
# Why 1024 (v5e, PERF.md section 6, PR 27): at the benchmark's training
# shape, 16 x 12 heads x 1,024 x 64 in bfloat16, causal, forward and
# backward of one layer take 2.8 ms through the flash kernels and 5.8 ms
# through the einsum formulation, whose compiled program plans 0.96 GB
# where the kernels' plans 0.35 GB (tools/bench_pallas.py --mode=fwdbwd
# --seqs 1024 --batch 16 --heads 12).  Until PR 27 the kernels took
# 21 ms there (float32 products, 128-row tiles) and the einsum path would
# have won; nothing shorter than 1,024 has been timed on the chip, so the
# threshold stays where the measurement is.
_FLASH_MIN_SEQ = int(os.environ.get("MXNET_FLASH_MIN_SEQ", "1024"))

# Backward implementation above the threshold: the fused Pallas kernels
# (default), or the pre-r6 rematerializing einsum vjp (fallback knob,
# e.g. to A/B the kernels on new hardware).  Frozen at import for the
# same jit-cache reason as the threshold.
_FLASH_BWD = os.environ.get("MXNET_TPU_FLASH_BWD", "pallas")

def _per_mesh_shard(fn):
    """``fn`` over each device's batch shard, when the program being
    traced spans a multi-device mesh (identity otherwise).  GSPMD cannot
    partition a Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"), so under a
    multi-device trainer the flash calls run manually per device: every
    operand — (B, T, H, D) tensors and the (B*H, 1, T) logsumexp alike —
    splits along its batch-major leading dim over dp and is whole over
    every other axis.  The trainer says so by tracing inside jax's mesh
    context (ShardedTrainer._tracing_on_mesh); the MeshSpec it armed names
    the dp axis."""
    from ..parallel.mesh import current_mesh
    spec = current_mesh()
    traced_on = jax.sharding.get_abstract_mesh()
    if spec is None or traced_on.size <= 1 or \
            traced_on != spec.mesh.abstract_mesh:
        return fn
    from jax.sharding import PartitionSpec as P
    # check_vma off: off-TPU the Pallas interpreter's grid loop fails the
    # varying-axes check (see sparse/embedding.py)
    return jax.shard_map(fn, mesh=spec.mesh, in_specs=P(spec.dp_axis),
                         out_specs=P(spec.dp_axis), check_vma=False)


@register("_contrib_fused_attention", inputs=("query", "key", "value"),
          params=dict(causal=attr_bool(False), scale=attr_float(0.0),
                      block_q=attr_int(0), flash_min_seq=attr_int(0),
                      window=attr_int(0)),
          aliases=("fused_attention",))
def _contrib_fused_attention(attrs, q, k, v):
    """Attention over (B, T, H, D); dispatches by sequence length.

    ``key`` and ``value`` may carry fewer heads than ``query``, (B, T, G, D)
    with G dividing H: query head n reads key/value head n // (H // G) and
    their gradients are summed over the group.  ``window`` (0 = none, needs
    ``causal``): query i sees keys i - window < j <= i; the kernels skip
    the key blocks wholly outside that band.

    Short sequences (T < flash_min_seq, default 1024, env
    MXNET_FLASH_MIN_SEQ) run the plain einsum formulation end-to-end:
    XLA fuses it well and residuals fit in HBM at tiny T.  At and above
    the threshold both directions run the Pallas flash kernels —
    K/V-blocked online-softmax forward saving the row logsumexp, and a
    dQ/dK/dV backward that rebuilds its score tiles from that residual —
    so HBM never holds a (T, T) tensor in either direction (reach T=32k+
    single chip; tools/bench_pallas.py --mode=fwdbwd for the table).
    The kernels' products run in the dtype of q/k/v, accumulated in
    float32.  ``block_q``: 0 = autotuned (ops/autotune.py cache, then
    ``autotune.DEFAULT_FLASH_BLOCKS``); explicit values win and are fitted
    to T (on the chip: a multiple of 128, or all of T).
    MXNET_TPU_FLASH_BWD=remat restores the pre-r6 rematerializing einsum
    backward."""
    scale = attrs.scale if attrs.scale > 0 else 1.0 / float(q.shape[-1]) ** 0.5
    causal = attrs.causal
    window = attrs.window
    if window < 0 or (window and not causal):
        raise MXNetError("fused_attention: window must be >= 0 and needs "
                         "causal=True, got %d" % window)
    block_q = attrs.block_q
    if block_q < 0:
        raise MXNetError("fused_attention: block_q must be >= 0 "
                         "(0 = autotuned), got %d" % block_q)
    block_q = block_q or None          # 0 -> consult the autotune cache

    def naive(q, k, v):
        B, Tq, H, D = q.shape
        Tk, G = k.shape[1:3]
        if G == H:
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        else:   # a group's H // G query heads against its one key/value head
            s = jnp.einsum("bqgrd,bkgd->bgrqk",
                           q.reshape(B, Tq, G, H // G, D), k) * scale
        if causal:
            gap = jnp.arange(Tq)[:, None] - jnp.arange(Tk)[None, :]
            seen = gap >= 0
            if window:
                seen = seen & (gap < window)
            s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if G == H:
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(B, Tq, H, D)

    flash_min = attrs.flash_min_seq or _FLASH_MIN_SEQ
    if q.shape[1] < flash_min:
        return naive(q, k, v)

    from . import pallas_kernels as pk
    # the kernels fit block_q/block_k to T themselves
    kw = dict(causal=causal, scale=scale, block_q=block_q, window=window)

    @jax.custom_vjp
    def attn(q, k, v):
        return _per_mesh_shard(functools.partial(
            pk.fused_attention, **kw))(q, k, v)

    def fwd(q, k, v):
        out, lse = _per_mesh_shard(functools.partial(
            pk.fused_attention_fwd, **kw))(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        if _FLASH_BWD == "pallas":
            return _per_mesh_shard(functools.partial(
                pk.fused_attention_bwd, **kw))(*res, g)
        q, k, v, _out, _lse = res
        # fallback: rematerialize through the einsum formulation
        _, vjp = jax.vjp(naive, q, k, v)
        return vjp(g)

    attn.defvjp(fwd, bwd)
    return attn(q, k, v)


# ---------------------------------------------------------------------------
# One chip's share of a top-k expert layer (parallel/moe.py) as a graph op
# ---------------------------------------------------------------------------

@register("_contrib_moe_ffn",
          inputs=("data", "router_weight", "shared_w1", "shared_w3",
                  "shared_w2", "expert_w1", "expert_w3", "expert_w2",
                  "expert_bias", "expert_load"),
          params=dict(num_experts=attr_int(required=True),
                      experts_held=attr_int(required=True),
                      first_expert=attr_int(0), top_k=attr_int(1),
                      num_hidden=attr_int(required=True),
                      route_norm=attr_bool(True), route_scale=attr_float(1.0),
                      bias_update_rate=attr_float(0.001)),
          num_outputs=3, num_visible_outputs=1,
          writeback={8: 1, 9: 2}, aux_inputs=(8, 9), mode_dependent=True,
          aliases=("moe_ffn",))
def _contrib_moe_ffn(attrs, x, wr, s1, s3, s2, w1, w3, w2, bias, load):
    """Sigmoid top-k routed experts with one shared expert over (..., d)
    tokens, as the chip that holds experts ``first_expert`` ..
    ``first_expert + experts_held`` of ``num_experts`` computes it
    (:func:`mxnet_tpu.parallel.moe.moe_ffn_held`): routing over all of
    them, no capacity and no drop, the absent experts' part left out.

    Auxiliary states, both over ALL experts: ``expert_bias`` (the
    selection bias of auxiliary-loss-free balancing) and ``expert_load``
    (tokens routed to each expert by the last training step).  A training
    forward writes this step's load and moves the bias by
    ``bias_update_rate * sign(mean(load) - load)``; the output uses the
    bias it was given."""
    from ..parallel import moe
    m = x.reshape(-1, x.shape[-1])
    out, n = moe.moe_ffn_held(
        m, wr, bias, (s1, s3, s2), (w1, w3, w2),
        num_experts=attrs.num_experts, first_expert=attrs.first_expert,
        top_k=attrs.top_k, route_norm=attrs.route_norm,
        route_scale=attrs.route_scale)
    if attrs.get("_train", False):
        n = jax.lax.stop_gradient(n)
        bias, load = moe.balanced_bias(bias, n, attrs.bias_update_rate), n
    return out.reshape(x.shape), bias, load
