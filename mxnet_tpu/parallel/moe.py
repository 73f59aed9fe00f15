"""Expert parallelism (MoE) over the 'ep' mesh axis.

Completes the SURVEY §2.3 parallelism matrix (the reference has no MoE —
this is TPU-native new work, like ring.py/pipeline.py).  Switch-style
top-1 routing in the GShard dispatch/combine-mask formulation: per shard,
routing builds a (tokens, experts, capacity) one-hot dispatch tensor, the
token block is exchanged between devices with ONE lax.all_to_all each way
(riding ICI), each device runs only its local experts, and a combine mask
weighted by the gate probability reassembles the output.  Tokens over an
expert's capacity are dropped (standard switch behavior) and a
load-balancing auxiliary loss keeps routing uniform.

Public API:
  moe_ffn(x, wg, w1, w2, mesh, axis='ep', capacity_factor=1.25,
          activation=relu)
      x: (tokens, d) global, sharded over `axis`; wg: (d, E) replicated;
      w1: (E, d, hidden), w2: (E, hidden, d) sharded over experts.
      Returns (out (tokens, d), aux_loss scalar).
  moe_ffn_dense(...) — single-device exact reference (no capacity drops),
      used by tests and as the n=1 fallback.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["moe_ffn", "moe_ffn_dense", "top1_gating"]


def top1_gating(logits, capacity: int):
    """Switch top-1 routing for one token shard.

    logits: (T, E).  Returns (dispatch (T,E,C) 0/1, combine (T,E,C) float,
    aux_loss scalar).  Position-in-expert comes from a cumsum over the
    one-hot assignment; tokens whose position exceeds `capacity` are
    dropped (their dispatch row is all zero, so they pass through as 0 —
    callers usually add a residual connection)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                      # (T,)
    gate = jnp.max(probs, axis=-1)                           # (T,)
    onehot = jax.nn.one_hot(expert, E, dtype=logits.dtype)   # (T, E)
    # load-balance loss (Switch eq. 4): E * sum_e f_e * P_e
    frac = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    pos = jnp.cumsum(onehot, axis=0) * onehot                # 1-based slots
    keep = (pos > 0) & (pos <= capacity)
    slot = jnp.clip(pos - 1, 0, capacity - 1).astype(jnp.int32)
    dispatch = jnp.where(
        keep[..., None],
        jax.nn.one_hot(slot, capacity, dtype=logits.dtype),
        0.0)                                                 # (T, E, C)
    combine = dispatch * gate[:, None, None]
    return dispatch, combine, aux


def _expert_ffn(blocks, w1, w2, activation):
    """blocks: (E_local, C_total, d); w1 (E_local, d, h); w2 (E_local, h, d)."""
    h = jnp.einsum("ecd,edh->ech", blocks, w1)
    h = activation(h)
    return jnp.einsum("ech,ehd->ecd", h, w2)


def _moe_local_fn(axis: str, capacity: int, activation):
    def fn(x, wg, w1, w2):
        # x: (T_local, d) this device's tokens; w1/w2: local expert slices
        logits = x @ wg                                      # (T_l, E)
        dispatch, combine, aux = top1_gating(logits, capacity)
        # pack per-expert token blocks, then ONE all-to-all: expert axis
        # scatters across devices, received blocks stack along capacity
        packed = jnp.einsum("tec,td->ecd", dispatch, x)      # (E, C, d)
        recv = lax.all_to_all(packed, axis, split_axis=0, concat_axis=1,
                              tiled=True)                    # (E_l, n*C, d)
        done = _expert_ffn(recv, w1, w2, activation)
        back = lax.all_to_all(done, axis, split_axis=1, concat_axis=0,
                              tiled=True)                    # (E, C, d)
        out = jnp.einsum("tec,ecd->td", combine, back)
        aux = lax.pmean(aux, axis)
        return out, aux
    return fn


def moe_ffn_dense(x, wg, w1, w2, activation=jax.nn.relu):
    """Exact single-device reference: every token goes through its argmax
    expert, no capacity limit.  O(T*E) compute — test/fallback only."""
    probs = jax.nn.softmax(x @ wg, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    h = activation(jnp.einsum("td,edh->teh", x, w1))
    all_out = jnp.einsum("teh,ehd->ted", h, w2)              # (T, E, d)
    picked = jnp.take_along_axis(
        all_out, expert[:, None, None].repeat(x.shape[-1], -1), 1)[:, 0]
    frac = jax.nn.one_hot(expert, wg.shape[1]).mean(axis=0)
    aux = wg.shape[1] * jnp.sum(frac * probs.mean(axis=0))
    return picked * gate[:, None], aux


def moe_ffn(x, wg, w1, w2, mesh: Mesh, axis: str = "ep",
            capacity_factor: float = 1.25, activation=jax.nn.relu):
    """Sharded gated expert FFN.  x (tokens, d) is sharded over `axis`;
    experts (w1/w2 leading axis) are sharded over `axis`; wg replicated.
    Returns (out, aux_loss); out keeps x's sharding.

    ``mesh`` may be a Mesh or MeshSpec and may carry other axes (the
    unified dp×tp×…×ep mesh): the shard_map — retained hand-written
    because the dispatch/combine all_to_all pair is a schedule the
    partitioner cannot derive from shardings — is manual only over
    ``axis`` and so composes with the GSPMD-managed axes."""
    from .placement import as_mesh
    mesh = as_mesh(mesh)
    n_dev = mesh.shape[axis]
    E = wg.shape[1]
    T = x.shape[0]
    if T % n_dev or E % n_dev:
        raise ValueError("tokens (%d) and experts (%d) must divide the "
                         "'%s' axis size %d" % (T, E, axis, n_dev))
    if n_dev == 1:
        return moe_ffn_dense(x, wg, w1, w2, activation)
    t_local = T // n_dev
    capacity = max(1, math.ceil(t_local * capacity_factor / E))
    fn = _moe_local_fn(axis, capacity, activation)
    sharded = shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis)),
        out_specs=(P(axis), P()))
    from .. import telemetry as _tel
    from ..resilience import watchdog as _wd
    from .audit import record_collective
    # each all_to_all moves the packed (E, C, d) dispatch blocks, f32
    a2a_bytes = 2 * E * capacity * x.shape[-1] * 4
    with _tel.span("collective/moe_ffn", cat="collective",
                   metric="parallel.collective_seconds",
                   kind="all-to-all,all-reduce", bytes=a2a_bytes), \
            _wd.watch("parallel.moe_ffn", kind="collective"):
        out = sharded(x, wg, w1, w2)
    # two all_to_all hops (dispatch + combine) AND the aux-loss pmean —
    # the trail must name every kind in the traced schedule (audit-trail
    # gap caught by analysis/graphcheck collective extraction)
    record_collective("all-to-all", "parallel.moe_ffn dispatch/combine",
                      bytes=a2a_bytes)
    record_collective("all-reduce", "parallel.moe_ffn aux-loss pmean",
                      bytes=4)
    from ..telemetry import perf as _perf
    _perf.maybe_attribute_fn(sharded, (x, wg, w1, w2), "moe_ffn",
                             n_devices=n_dev, mesh=mesh)
    return out
