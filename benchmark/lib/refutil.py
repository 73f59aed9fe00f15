"""Small helpers the plain references share (nothing of the program)."""
import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key from any whole number (the driver's exceed 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def norms(tree):
    """Euclidean norm of each named device array."""
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def nudged(tree, share, seed):
    """Every element moved by ``share`` of itself times a standard normal
    draw: the start of the look at how far a model carries a change of
    rounding's size into its gradients (calibrate.py --nudge)."""
    keys = jax.random.split(seed_key(seed + 7), len(tree))
    return {k: v * (1.0 + share * jax.random.normal(key, v.shape, v.dtype))
            for key, (k, v) in zip(keys, sorted(tree.items()))}
