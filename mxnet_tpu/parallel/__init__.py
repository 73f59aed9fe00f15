"""Parallelism & distribution over TPU meshes.

This package is the TPU-native replacement for the reference's entire
distribution stack (SURVEY.md §2.3, §5.8): ps-lite/NCCL/CUDA-P2P become XLA
collectives over a jax.sharding.Mesh (ICI intra-slice, DCN across slices).

Modules:
* mesh.py  — ONE named-axis mesh (arbitrary dp/tp/pp/sp/ep layouts via
  MeshSpec.build) + the current-mesh thread-local
* placement.py — the unified placement rules: ``__shard__`` grammar,
  tp recipe, ZeRO state sharding, batch specs (NamedSharding everywhere;
  jit/GSPMD inserts and fuses the collectives)
* trainer.py — sharded train step (dp/tp via GSPMD + the ZeRO sharded
  weight update: reduce-scatter → shard-local update → weight all-gather)
* ring.py / moe.py / pipeline.py — the retained hand-written shard_map
  kernels (ring attention, MoE dispatch, the GPipe tick schedule: the
  programs the partitioner cannot derive), embedded in the same mesh so
  they compose with the GSPMD axes
* audit.py — collective accounting: per-kind AND per-axis payload bytes
  from compiled HLO, with fused all-reduce+slice classified as the
  reduce-scatter it is on the wire
* hierarchy.py — the two-tier (in-island fast / cross-island slow)
  hierarchical all-reduce for multi-pod shapes, audited per tier against
  ``audit.hierarchical_allreduce_model_bytes``
"""
from __future__ import annotations

from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

from .mesh import (MeshSpec, current_mesh, data_parallel_mesh, make_mesh,
                   reform_mesh, set_current_mesh, shard_batch, replicate)

Topology = namedtuple("Topology", ["process_index", "process_count",
                                   "local_device_count",
                                   "global_device_count"])


def topology() -> Topology:
    return Topology(jax.process_index(), jax.process_count(),
                    jax.local_device_count(), jax.device_count())


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None):
    """Multi-host bootstrap — the tracker/Postoffice analog (reference
    tools/launch.py + ps::Postoffice).  On TPU pods the env provides the
    coordination, so arguments are optional.

    Under tools/launch.py (local multi-process testing, the dmlc-tracker
    local-mode analog) the DMLC_*/MXNET_TPU_* env protocol supplies the
    coordinator and rank, and a cpu backend with gloo collectives is
    configured so DCN logic runs without a pod."""
    import os
    # jax 0.9.0 has no public spelling of "is a backend up?", and
    # jax.process_count() would itself initialise one
    from jax._src import xla_bridge
    if jax.distributed.is_initialized() or \
            xla_bridge.backends_are_initialized():
        return  # too late to (re)initialise; the runtime already decided
    coordinator_address = coordinator_address or \
        os.environ.get("MXNET_TPU_COORDINATOR")
    if num_processes is None and "DMLC_NUM_WORKER" in os.environ:
        num_processes = int(os.environ["DMLC_NUM_WORKER"])
    if process_id is None and "DMLC_WORKER_ID" in os.environ:
        process_id = int(os.environ["DMLC_WORKER_ID"])
    if coordinator_address and (num_processes or 0) > 1:
        if os.environ.get("MXNET_TPU_DIST_DEVICE", "cpu") == "cpu":
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # a rank that cannot join its gang must not train on alone
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
        return
    try:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    except ValueError:
        # no coordinator given and no cluster environment jax can detect:
        # this is a single-process run.  Anything else — a coordinator
        # that cannot be reached, a rank mismatch — propagates.
        pass


def barrier(name="kvstore_barrier"):
    """Global barrier (reference KVStore::Barrier, kvstore.h:349).

    Watchdog-armed: a rank that never arrives leaves the others blocked
    here forever, so the deadline turns that silence into a stack dump +
    post-mortem + fail-fast (resilience/watchdog.py)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        from ..resilience import watchdog as _wd
        from .audit import record_collective
        with _wd.watch("parallel.barrier(%s)" % name, kind="collective"):
            multihost_utils.sync_global_devices(name)
        record_collective("barrier", name)


def allreduce_array(x):
    """Sum an array across processes (DCN allreduce).  Within one process
    the kvstore already reduced device copies; this extends the reduction
    across hosts like the reference's server-side aggregation."""
    if jax.process_count() == 1:
        return x
    from jax.experimental import multihost_utils
    from ..resilience import watchdog as _wd
    from .audit import record_collective
    with _wd.watch("parallel.allreduce_array", kind="collective"):
        gathered = multihost_utils.process_allgather(x)
        out = jnp.sum(gathered, axis=0)
    record_collective("all-reduce", "parallel.allreduce_array",
                      bytes=int(getattr(x, "nbytes", 0)))
    return out


def allreduce_row_sparse(rs):
    """Union-sum a RowSparseNDArray across processes without densifying
    (the reference's sparse push aggregation, kvstore_dist_server.h:223).
    nnz differs per rank, so rows are padded to the global max (padding
    ids = -1), allgathered, and merged."""
    if jax.process_count() == 1:
        return rs
    from jax.experimental import multihost_utils
    from ..ndarray.sparse import RowSparseNDArray, merge_row_sparse
    from ..resilience import watchdog as _wd
    from .audit import record_collective
    with _wd.watch("parallel.allreduce_row_sparse", kind="collective"):
        out = _allreduce_row_sparse_impl(rs, multihost_utils,
                                         RowSparseNDArray, merge_row_sparse)
    record_collective("all-gather", "parallel.allreduce_row_sparse")
    return out


def _allreduce_row_sparse_impl(rs, multihost_utils, RowSparseNDArray,
                               merge_row_sparse):
    nnz = rs._data.shape[0]
    max_nnz = int(np.max(multihost_utils.process_allgather(
        jnp.asarray([nnz]))))
    pad = max_nnz - nnz
    data = jnp.pad(rs._data, [(0, pad)] + [(0, 0)] * (rs._data.ndim - 1))
    idx = jnp.pad(rs._indices, (0, pad), constant_values=-1)
    all_data = multihost_utils.process_allgather(data)
    all_idx = np.asarray(multihost_utils.process_allgather(idx))
    parts = []
    for p in range(all_idx.shape[0]):
        keep = all_idx[p] >= 0
        if not np.any(keep):
            continue
        parts.append(RowSparseNDArray(
            jnp.asarray(np.asarray(all_data[p])[keep]),
            jnp.asarray(all_idx[p][keep]), rs.shape))
    return merge_row_sparse(parts) if parts else rs
