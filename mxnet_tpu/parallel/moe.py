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
  moe_ffn_held(...) — ONE chip's share of a top-k layer under expert
      parallelism (second half of this file): routes over all experts,
      computes the experts it is told it holds, drops nothing, and runs
      without the exchange that would bring it other chips' tokens.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.pallas_kernels import grouped_matmul

__all__ = ["moe_ffn", "moe_ffn_dense", "top1_gating", "route_top_k",
           "held_experts_ffn", "moe_ffn_held", "row_buckets", "expert_load",
           "balanced_bias"]


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


# ---------------------------------------------------------------------------
# One chip's share of a drop-free top-k expert layer
# ---------------------------------------------------------------------------
#
# The layer is told which experts it holds (``first_expert`` ..
# ``first_expert + held``) out of the ``E`` the router scores.  Every token
# is routed over all E; the picks that fall on a held expert are sorted by
# expert, their rows gathered, pushed through the gated FFN of their expert
# as three grouped products (``ops/pallas_kernels.grouped_matmul``: on the
# TPU a kernel whose grid walks the row tiles the groups own), and added
# back weighted.  What the absent experts would add is left out; nothing
# stands in for them or for the exchange.
#
# No capacity, no drop: a token's k picks may all land here, so the sorted
# buffers must admit ``T * min(k, held)`` rows while the expected load is
# ``T * k * held / E``.  Time follows the rows that are live, not that
# worst case: the live count picks one of a few static row budgets
# (:func:`row_buckets`) through ``lax.switch``, and gather, products and
# combine all run at that budget.  The backward pass rebuilds the experts'
# hidden activations inside the same switch (custom_vjp), so no
# budget-sized buffer is kept between the passes.


def row_buckets(tokens: int, top_k: int, held: int, num_experts: int):
    """Static row budgets, ascending: 1.25 x the expected live rows, then
    doubling, up to the worst case ``tokens * min(top_k, held)``."""
    worst = tokens * min(top_k, held)
    rows = -(-5 * tokens * top_k * held // (4 * num_experts))
    rows = min(-(-rows // 8) * 8, worst)
    out = []
    while rows < worst:
        out.append(rows)
        rows *= 2
    return tuple(out) + (worst,)


def route_top_k(m, wr, bias, top_k: int, route_norm: bool = True,
                route_scale: float = 1.0):
    """Sigmoid routing with a selection bias (auxiliary-loss-free
    balancing, arXiv:2408.15664): scores ``s = sigmoid(m·Wr)`` in float32,
    the ``top_k`` largest of ``s + bias`` picked, weights from ``s`` alone,
    normalised over the picks if ``route_norm`` and scaled.
    Returns (picks (T, k) int32, weights (T, k) float32)."""
    s = jax.nn.sigmoid(jnp.dot(m, wr, preferred_element_type=jnp.float32))
    _, picks = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)),
                         top_k)
    w = jnp.take_along_axis(s, picks, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return picks.astype(jnp.int32), w * route_scale


def expert_load(picks, num_experts: int):
    """Tokens routed to each of ALL experts this step: (E,) float32."""
    hit = picks.reshape(-1, 1) == jnp.arange(num_experts, dtype=picks.dtype)
    return jnp.sum(hit, axis=0, dtype=jnp.float32)


def balanced_bias(bias, load, rate: float):
    """``b + rate * sign(mean(n) - n)``: an expert over the mean load is
    made less likely to be picked next step, one under it more."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def _gated(h1, h3):
    return (jax.nn.silu(h1.astype(jnp.float32))
            * h3.astype(jnp.float32)).astype(h1.dtype)


def _sorted_picks(picks, first_expert: int, held: int):
    """The picks that fall on a held expert, sorted by expert.  Returns
    ``order`` (T*k,): the flat pick (token * k + slot) at each sorted row,
    the live rows first; ``sizes`` (held,): rows each held expert owns."""
    local = picks.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype),
                    axis=0, dtype=jnp.int32)
    return order, sizes


def _held_rows(m, c, w1, w3, w2, order, sizes, *, rows: int):
    """The held experts' part at a static budget of ``rows`` sorted rows
    (the caller has made sure the live rows fit): (T, d) float32."""
    T, d = m.shape
    with jax.named_scope("dispatch"):
        order = order[:rows]
        tok = order // c.shape[1]
        live = (jnp.arange(rows, dtype=jnp.int32) < jnp.sum(sizes))[:, None]
        xs = jnp.where(live, m[tok], 0)
    with jax.named_scope("experts"):
        # no kernel writes the rows past the live ones: what they hold is
        # whatever the buffer held before, so every result is masked
        # before it is used (and, by the transpose, every gradient)
        g = _gated(jnp.where(live, grouped_matmul(xs, w1, sizes), 0),
                   jnp.where(live, grouped_matmul(xs, w3, sizes), 0))
        y = grouped_matmul(g, w2, sizes, jnp.float32)
    with jax.named_scope("combine"):
        y = jnp.where(live, y * c.reshape(-1)[order][:, None], 0)
        return jnp.zeros((T, d), jnp.float32).at[tok].add(y)


def held_experts_ffn(m, c, picks, w1, w3, w2, first_expert: int,
                     buckets=None, num_experts=None):
    """``sum_{e in picks[t], e held} c[t, e] * expert_e(m[t])`` for the
    experts ``first_expert .. first_expert + w1.shape[0]``, each
    ``(silu(x·W1) * (x·W3))·W2``; (T, d) float32.  ``buckets``: the static
    row budgets (default :func:`row_buckets`)."""
    T, k = picks.shape
    held = w1.shape[0]
    if buckets is None:
        buckets = row_buckets(T, k, held, num_experts or held)
    buckets = tuple(int(b) for b in buckets)
    if buckets[-1] < T * min(k, held):
        raise ValueError("the last row budget (%d) must admit every pick "
                         "(%d): nothing is dropped" % (buckets[-1],
                                                       T * min(k, held)))
    with jax.named_scope("route"):
        order, sizes = _sorted_picks(picks, first_expert, held)
        which = jnp.sum(jnp.sum(sizes) > jnp.asarray(buckets[:-1], jnp.int32),
                        dtype=jnp.int32)
    parts = [functools.partial(_held_rows, rows=b) for b in buckets]

    def switch(which, fns, *operands):
        if len(fns) == 1:
            return fns[0](*operands)
        return lax.switch(which, fns, *operands)

    def grads(part):
        def fn(dy, m, c, w1, w3, w2, order, sizes):
            _, vjp = jax.vjp(lambda *diff: part(*diff, order, sizes),
                             m, c, w1, w3, w2)
            return vjp(dy)
        return fn

    @jax.custom_vjp
    def run(m, c, w1, w3, w2, order, sizes, which):
        return switch(which, parts, m, c, w1, w3, w2, order, sizes)

    def fwd(*operands):
        return run(*operands), operands

    def bwd(saved, dy):
        # the forward again at the same budget, and its transpose: nothing
        # budget-sized lives between the two passes
        return switch(saved[-1], [grads(p) for p in parts], dy,
                      *saved[:-1]) + (None, None, None)

    run.defvjp(fwd, bwd)
    return run(m, c, w1, w3, w2, order, sizes, which)


def moe_ffn_held(m, wr, bias, shared, experts, *, num_experts: int,
                 first_expert: int, top_k: int, route_norm: bool = True,
                 route_scale: float = 1.0, buckets=None, live=None):
    """One chip's share of the layer over tokens ``m`` (T, d): routing over
    all ``num_experts`` (``wr`` (d, E), ``bias`` (E,)), the held experts'
    part, and the shared expert beside it.  ``experts`` = (W1, W3, W2) of
    shapes (held, d, f), (held, d, f), (held, f, d); ``shared`` likewise
    without the leading axis, or None.  ``live`` (T,) bool, optional: rows
    that are padding of a fixed-size step (a serving step's unused rows)
    pick no expert, so they own no sorted row and count in no load.
    Returns (out (T, d) in m's dtype, load (E,) float32)."""
    with jax.named_scope("route"):
        picks, c = route_top_k(m, wr, bias, top_k, route_norm, route_scale)
        if live is not None:
            # an expert number nobody holds
            picks = jnp.where(live[:, None], picks, num_experts)
        load = expert_load(picks, num_experts)
    out = held_experts_ffn(m, c, picks, *experts, first_expert=first_expert,
                           buckets=buckets, num_experts=num_experts)
    if shared is not None:
        with jax.named_scope("shared"):
            s1, s3, s2 = shared
            out = out + jnp.dot(_gated(m @ s1, m @ s3), s2,
                                preferred_element_type=jnp.float32)
    return out.astype(m.dtype), load
