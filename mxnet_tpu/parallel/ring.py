"""Ring-attention sequence/context parallelism.

Beyond-reference capability (SURVEY.md §5.7: the reference predates
attention; long-context parallelism here is new work designed for ICI).

Implementation: q/k/v are sharded along the sequence axis over the 'sp'
mesh axis.  Each device holds one sequence block; k/v blocks rotate around
the ring via lax.ppermute while each device accumulates its queries'
attention over every block with numerically-stable online softmax (the
flash/blockwise formulation) — compute overlaps the ICI transfer, HBM
never holds the full (T, T) score matrix, and sequence length scales
linearly with the number of devices.

Public API:
  ring_attention(q, k, v, mesh, axis='sp', causal=False, scale=None)
    q/k/v: (B, T, H, D) global arrays (host or sharded); returns same shape.
  local_ring_attention_fn(...)  — the shard_map'd function for embedding in
    larger sharded programs (e.g. a transformer train step).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

__all__ = ["ring_attention", "local_ring_attention_fn"]


def _block_attn(q, k, v, mask, scale):
    """One (Tq, Tk) block: returns (unnormalised out, row max, row sumexp)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                     # (B,H,Tq); -inf if all masked
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)                     # (B,H,Tq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, m, l


def local_ring_attention_fn(axis_name: str, causal: bool, scale: float,
                            num_devices: int):
    """Returns fn(q_blk, k_blk, v_blk) for use inside shard_map over
    `axis_name`; blocks are the per-device sequence shards."""

    def fn(q, k, v):
        my_idx = jax.lax.axis_index(axis_name)
        B, Tq, H, D = q.shape
        Tk = k.shape[1]
        perm = [(j, (j + 1) % num_devices) for j in range(num_devices)]

        def block(i, k_blk, v_blk):
            # which global block do we hold? blocks rotate j -> j+1 each
            # step, so at step i device j holds block (j - i) mod n
            blk_idx = (my_idx - i) % num_devices
            if causal:
                q_pos = my_idx * Tq + jnp.arange(Tq)
                k_pos = blk_idx * Tk + jnp.arange(Tk)
                mask = q_pos[:, None] >= k_pos[None, :]
                mask = mask[None, None]  # (1,1,Tq,Tk)
            else:
                mask = None
            return _block_attn(q, k_blk, v_blk, mask, scale)

        def merge(acc, blk):
            # online softmax merge; -inf maxima (fully-masked so far)
            # guarded
            o_acc, m_acc, l_acc = acc
            o, m, l = blk
            new_m = jnp.maximum(m_acc, m)
            new_m_safe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
            alpha = jnp.where(jnp.isfinite(m_acc),
                              jnp.exp(m_acc - new_m_safe), 0.0)
            beta = jnp.where(jnp.isfinite(m),
                             jnp.exp(m - new_m_safe), 0.0)
            l_new = l_acc * alpha + l * beta
            o_new = o_acc * alpha[..., None].swapaxes(1, 2) + \
                o * beta[..., None].swapaxes(1, 2)
            return (o_new, new_m, l_new)

        def step(carry, i):
            k_blk, v_blk, o_acc, m_acc, l_acc = carry
            # double-buffered ring step: block i+1's rotation is issued
            # BEFORE block i's attention, and neither depends on the
            # other — the ICI hop flies while the MXU works (the static
            # overlap instrument proves the schedulability; an async
            # backend realizes it as -start/compute/-done)
            k_next = jax.lax.ppermute(k_blk, axis_name, perm)
            v_next = jax.lax.ppermute(v_blk, axis_name, perm)
            o_new, new_m, l_new = merge((o_acc, m_acc, l_acc),
                                        block(i, k_blk, v_blk))
            return (k_next, v_next, o_new, new_m, l_new), None

        # derive initial accumulators from q so they carry the same
        # shard_map varying axes (and dtype) as the loop outputs
        o0 = jnp.zeros_like(q)
        m0 = jnp.swapaxes(q[..., 0] * 0 - jnp.inf, 1, 2)   # (B,H,Tq)
        l0 = jnp.swapaxes(q[..., 0] * 0, 1, 2)
        (k, v, o, m, l), _ = jax.lax.scan(
            step, (k, v, o0, m0, l0), jnp.arange(num_devices - 1))
        # the LAST block needs no rotation: the old n-step loop's final
        # ppermute only carried k/v home to be discarded — 1/n of the
        # ring's wire bytes for nothing (and n=1 paid a pointless
        # self-permute)
        o, m, l = merge((o, m, l), block(num_devices - 1, k, v))
        l_t = jnp.swapaxes(l, 1, 2)[..., None]   # (B,Tq,H,1)
        return o / jnp.maximum(l_t, 1e-20)

    return fn


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = False, scale: Optional[float] = None):
    """Multi-device attention over sequence-sharded q/k/v.

    q/k/v: (B, T, H, D); T must divide by mesh.shape[axis].

    ``mesh`` may be a Mesh or MeshSpec and may carry OTHER axes beyond
    ``axis`` (the unified dp×tp×…×sp mesh): the shard_map is manual only
    over the names its specs mention, so this kernel — retained
    hand-written because the blockwise online-softmax ring schedule
    beats anything the partitioner derives — embeds in the same mesh as
    the GSPMD-managed axes and composes with them."""
    from .placement import as_mesh
    mesh = as_mesh(mesh)
    n = mesh.shape[axis]
    if scale is None:
        scale = float(1.0 / np.sqrt(q.shape[-1]))
    fn = local_ring_attention_fn(axis, causal, scale, n)
    spec = P(None, axis, None, None)
    mapped = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    from .. import telemetry as _tel
    from ..resilience import watchdog as _wd
    from .audit import record_collective
    # k/v blocks each make n-1 ppermute hops around the ring
    kv_bytes = int(getattr(k, "nbytes", 0) + getattr(v, "nbytes", 0))
    from ..telemetry import memory as _memory
    with _tel.span("collective/ring_attention", cat="collective",
                   metric="parallel.collective_seconds",
                   kind="collective-permute", bytes=kv_bytes), \
            _wd.watch("parallel.ring_attention", kind="collective"), \
            _memory.oom_guard("parallel.ring_attention",
                              program="ring_attention"):
        q = jax.device_put(q, sharding)
        k = jax.device_put(k, sharding)
        v = jax.device_put(v, sharding)
        _memory.tag((q, k, v), "activations", label="ring_attention.qkv")
        out = jax.jit(mapped)(q, k, v)
    record_collective("collective-permute", "parallel.ring_attention",
                      bytes=kv_bytes)
    from ..telemetry import perf as _perf
    _perf.maybe_attribute_fn(mapped, (q, k, v), "ring_attention",
                             n_devices=n, mesh=mesh)
    return out


def reference_attention(q, k, v, causal=False, scale=None):
    """Single-device reference for testing."""
    if scale is None:
        scale = float(1.0 / np.sqrt(q.shape[-1]))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
