"""The optimizer the training configurations state, written out plainly for
the references: SGD with momentum and weight decay on float32 arithmetic,

    m' = momentum * m - lr * (g + wd * p);   p' = store(p + m')

where ``store`` rounds to the type the configuration keeps that leaf in.
"""
import jax
import jax.numpy as jnp


@jax.jit
def _update(p, m, g, lr, momentum, wd):
    m2 = momentum * m - lr * (g + wd * p)
    return p + m2, m2


def sgd_momentum(params, mom, grads, lr, momentum, wd, store_dtypes):
    new_p, new_m = {}, {}
    for k in params:
        p2, m2 = _update(params[k], mom[k], grads[k], lr, momentum, wd)
        new_p[k] = p2.astype(store_dtypes[k]).astype(jnp.float32)
        new_m[k] = m2
    return new_p, new_m
