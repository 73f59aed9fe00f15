"""Two-tier hierarchical collectives for multi-pod shapes.

One ICI mesh ("island") has ~an order of magnitude more bandwidth than
the DCN/optical tier between islands, so a flat ring all-reduce over a
multi-pod mesh is priced by its slowest links: every byte of the
2(N-1)/N·P per-link ring traffic crosses the slow tier wherever the
ring does.  The two-tier schedule moves only a 1/k weight shard over
the slow tier instead:

1. **in-island reduce-scatter** over the fast axis — each of the k
   in-island ranks ends up owning the island-local sum of ONE 1/k shard;
2. **cross-island exchange** over the slow axis — for each shard,
   exactly one designated rank per island (the in-island rank that owns
   it) all-reduces that P/k shard with its peers in the other m-1
   islands; per designated rank the slow tier carries
   2(m-1)/m · P/k bytes, vs 2(N-1)/N · P on a flat ring's crossing
   link — a ~k× per-link reduction;
3. **in-island all-gather** over the fast axis — every rank reassembles
   the globally-reduced full tensor over fast links.

The audit side lives in parallel/audit.py
(``hierarchical_allreduce_model_bytes``): the compiled program's
per-tier payloads — attributed to mesh axes by the replica-group
labeler — must match this model exactly, which the 2-island×4 dryrun
(tests/test_hierarchy.py) asserts.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

__all__ = ["two_tier_psum", "hierarchical_allreduce", "flat_allreduce"]



def _count(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def two_tier_psum(v, fast_axis: str, fast_size: int, slow_axis: str):
    """The per-device two-tier all-reduce, for use INSIDE a shard_map
    whose mesh names both axes: reduce-scatter(fast) → psum(slow) on the
    1/k shard → all-gather(fast).  ``v`` is this device's local array;
    returns the global sum with ``v``'s shape.  Arrays whose element
    count does not divide ``fast_size`` are zero-padded for the scatter
    and trimmed after the gather."""
    shape = v.shape
    flat = v.reshape(-1)
    pad = (-flat.size) % max(1, fast_size)
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)])
    shard = jax.lax.psum_scatter(flat, fast_axis, scatter_dimension=0,
                                 tiled=True)
    shard = jax.lax.psum(shard, slow_axis)
    full = jax.lax.all_gather(shard, fast_axis, axis=0, tiled=True)
    if pad:
        full = full[:flat.size - pad]
    return full.reshape(shape)


def hierarchical_allreduce(stacked, mesh, slow_axis: str = "island",
                           fast_axis: str = "dp"):
    """All-reduce per-device values via the two-tier schedule.

    ``stacked`` has shape ``(world, ...)`` — row i is device i's local
    value (island-major device order, matching the mesh) — and the
    result has the same shape with every row equal to the global sum.
    ``mesh`` is a ``jax.sharding.Mesh`` (or MeshSpec) naming both axes.
    """
    mesh = getattr(mesh, "mesh", mesh)
    m = int(mesh.shape[slow_axis])
    k = int(mesh.shape[fast_axis])
    spec = P((slow_axis, fast_axis))

    def per_device(block):          # block: (1, ...) — this device's row
        out = two_tier_psum(block[0], fast_axis, k, slow_axis)
        return out[None]

    mapped = shard_map(per_device, mesh=mesh, in_specs=spec,
                       out_specs=spec)
    from ..resilience import watchdog as _wd
    from .audit import hierarchical_allreduce_model_bytes, \
        record_collective
    elem = jnp.dtype(stacked.dtype).itemsize
    payload = _count(stacked.shape) * elem // max(1, m * k)
    model = hierarchical_allreduce_model_bytes(payload, m, k,
                                               elem_bytes=elem)
    with _wd.watch("parallel.hierarchical_allreduce", kind="collective"):
        out = mapped(stacked)
    record_collective("reduce-scatter", "parallel.hierarchical fast tier",
                      bytes=model["reduce-scatter"])
    record_collective("all-reduce", "parallel.hierarchical slow tier",
                      bytes=model["all-reduce"])
    record_collective("all-gather", "parallel.hierarchical fast tier",
                      bytes=model["all-gather"])
    return out


def flat_allreduce(stacked, mesh, slow_axis: str = "island",
                   fast_axis: str = "dp"):
    """The flat (single-ring) baseline over the same stacked layout —
    one psum spanning both tiers; what the hierarchical schedule's
    slow-tier bytes are audited AGAINST."""
    mesh = getattr(mesh, "mesh", mesh)
    spec = P((slow_axis, fast_axis))

    def per_device(block):
        return jax.lax.psum(block[0], (slow_axis, fast_axis))[None]

    mapped = shard_map(per_device, mesh=mesh, in_specs=spec,
                       out_specs=spec)
    from ..resilience import watchdog as _wd
    from .audit import record_collective
    world = int(mesh.shape[slow_axis]) * int(mesh.shape[fast_axis])
    with _wd.watch("parallel.flat_allreduce", kind="collective"):
        out = mapped(stacked)
    record_collective(
        "all-reduce", "parallel.flat_allreduce",
        bytes=_count(stacked.shape) * jnp.dtype(stacked.dtype).itemsize
        // max(1, world))
    return out


def hierarchical_grad_allreduce(tree, mesh, slow_axis: str = "island",
                                fast_axis: str = "dp"):
    """Pytree convenience: :func:`hierarchical_allreduce` per leaf."""
    return jax.tree_util.tree_map(
        partial(hierarchical_allreduce, mesh=mesh, slow_axis=slow_axis,
                fast_axis=fast_axis), tree)
