"""Plain reference for the pre-activation bottleneck ResNets (He et al. 2016,
arXiv:1603.05027) as the reference framework's ``symbols/resnet.py`` builds
them for ImageNet: ``jax.numpy``/``lax`` in float32 at ``highest`` precision,
NCHW, batch statistics in training mode.  Imports nothing of the program;
parameter names are the program's so that one dict serves both.

Each residual unit is wrapped in ``jax.checkpoint`` so that the float32
activations of a 128-image batch fit on one chip: the reference goes layer by
layer, since BatchNorm's statistics span the whole batch and rows cannot be
split.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.refutil import norms, nudged, seed_key
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _unit_names(cfg):
    for stage, n in enumerate(cfg["units"]):
        for unit in range(n):
            yield stage, unit, "stage%d_unit%d" % (stage + 1, unit + 1)


def param_shapes(cfg):
    """name -> shape of the trainable leaves, and the BatchNorm layers'
    names with their channel counts (moving statistics)."""
    f = cfg["filter_list"]
    c_in = cfg["image_shape"][0]
    shapes, bns = {}, {}

    def bn(name, c):
        shapes[name + "_gamma"] = (c,)
        shapes[name + "_beta"] = (c,)
        bns[name] = c

    bn("bn_data", c_in)
    shapes["conv0_weight"] = (f[0], c_in, 7, 7)
    bn("bn0", f[0])
    cin = f[0]
    for stage, unit, name in _unit_names(cfg):
        cout, mid = f[stage + 1], f[stage + 1] // 4
        bn(name + "_bn1", cin)
        shapes[name + "_conv1_weight"] = (mid, cin, 1, 1)
        bn(name + "_bn2", mid)
        shapes[name + "_conv2_weight"] = (mid, mid, 3, 3)
        bn(name + "_bn3", mid)
        shapes[name + "_conv3_weight"] = (cout, mid, 1, 1)
        if unit == 0:
            shapes[name + "_sc_weight"] = (cout, cin, 1, 1)
        cin = cout
    bn("bn1", cin)
    shapes["fc1_weight"] = (cfg["num_classes"], cin)
    shapes["fc1_bias"] = (cfg["num_classes"],)
    return shapes, bns


def make_weights(cfg, seed):
    shapes, _ = param_shapes(cfg)

    @jax.jit
    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name.endswith("_gamma"):
                out[name] = 1.0 + 0.02 * z
            elif name.endswith(("_beta", "_bias")):
                out[name] = 0.02 * z
            else:
                fan_in = int(np.prod(shape[1:]))
                out[name] = math.sqrt(2.0 / fan_in) * z
        return out

    return build(seed_key(seed))


def _conv(x, w, stride, pad, q):
    return lax.conv_general_dilated(
        q(x), q(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)


def _bn(p, name, x, eps, fix_gamma=False):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    g = 1.0 if fix_gamma else p[name + "_gamma"].reshape(1, -1, 1, 1)
    return (x - mean) * lax.rsqrt(var + eps) * g \
        + p[name + "_beta"].reshape(1, -1, 1, 1)


def forward(p, images, cfg, cast=None):
    """Logits (N, classes) of uint8 NCHW images, training-mode BatchNorm."""
    eps = cfg["bn_eps"]

    def q(x):
        return x if cast is None else x.astype(cast).astype(jnp.float32)

    def stem(p, x):
        x = _bn(p, "bn_data", x, eps, fix_gamma=True)
        x = _conv(x, p["conv0_weight"], 2, 3, q)
        x = jax.nn.relu(_bn(p, "bn0", x, eps))
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                                 (1, 1, 2, 2),
                                 [(0, 0), (0, 0), (1, 1), (1, 1)])

    def unit(p, x, name, stride, project):
        a1 = jax.nn.relu(_bn(p, name + "_bn1", x, eps))
        y = _conv(a1, p[name + "_conv1_weight"], 1, 0, q)
        y = jax.nn.relu(_bn(p, name + "_bn2", y, eps))
        y = _conv(y, p[name + "_conv2_weight"], stride, 1, q)
        y = jax.nn.relu(_bn(p, name + "_bn3", y, eps))
        y = _conv(y, p[name + "_conv3_weight"], 1, 0, q)
        sc = _conv(a1, p[name + "_sc_weight"], stride, 0, q) if project else x
        return y + sc

    x = jax.checkpoint(stem)(p, images.astype(jnp.float32))
    for stage, u, name in _unit_names(cfg):
        stride = 2 if (u == 0 and stage > 0) else 1
        x = jax.checkpoint(functools.partial(
            unit, name=name, stride=stride, project=(u == 0)))(p, x)
    x = jax.nn.relu(_bn(p, "bn1", x, eps))
    x = jnp.mean(x, axis=(2, 3))
    return jnp.matmul(q(x), q(p["fc1_weight"]).T, precision=HIGHEST) \
        + p["fc1_bias"]


def summed_loss(p, images, labels, cfg, cast=None):
    logits = forward(p, images, cfg, cast=cast)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)
    return -jnp.sum(picked), jnp.sum(jnp.exp(logp))


def train_reference(cfg, seed, store_dtypes, batches, traffic, cast=None,
                    nudge=0.0):
    """The first ``len(batches)`` steps from the seed: each step's 'loss' as
    the program's step reports it (the sum of the softmax outputs), the first
    gradient's norm by leaf, the norm of each leaf's change.  Parameters are
    rounded to their stored type after every update."""
    from benchmark.lib.sgd import sgd_momentum
    tr = cfg["training"]

    @jax.jit
    def grad_fn(p, images, labels):
        (ce, probs), g = jax.value_and_grad(
            lambda p_: summed_loss(p_, images, labels, cfg, cast=cast),
            has_aux=True)(p)
        return ce, probs, g

    w = make_weights(cfg, seed)
    p0 = {k: v.astype(store_dtypes[k]).astype(jnp.float32)
          for k, v in w.items()}
    del w
    if nudge:       # calibrate.py's look at the model's own sensitivity
        p0 = nudged(p0, nudge, seed)
    p = p0
    m = {k: jnp.zeros_like(v) for k, v in p.items()}
    out = {"loss": [], "cross_entropy": []}
    for i, batch in enumerate(batches):
        ce, probs, g = grad_fn(p, np.asarray(batch["data"]),
                               np.asarray(batch["softmax_label"])
                               .astype(np.int32))
        if i == 0:
            out["grad_norms"] = norms(g)
        out["loss"].append(float(probs))
        out["cross_entropy"].append(float(ce))
        p, m = sgd_momentum(p, m, g, tr["lr"], tr["momentum"], tr["wd"],
                            store_dtypes)
        del g
    out["change_norms"] = norms({k: p[k] - p0[k] for k in p})
    return out
