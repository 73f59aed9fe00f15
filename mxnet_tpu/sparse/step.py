"""DLRM-style recommender training step over the sharded embedding plane.

The canonical millions-of-users workload (TensorFlow system paper,
PAPERS.md: sparse embedding layers as THE large-scale case): categorical
features hit big embedding tables a few rows per example, dense features
run through an MLP, and the interaction trains a click predictor.  This
module builds that step end-to-end IN ONE JIT over the unified mesh:

* tables row-sharded via :class:`~mxnet_tpu.sparse.embedding.
  ShardedEmbedding` (lookup = owner-shard routing, all-to-all bytes
  proportional to touched rows);
* the MLP replicated, batch dp-sharded — GSPMD inserts the dp grad
  all-reduce for the dense half exactly like ShardedTrainer;
* embedding gradients NEVER densify: the loss is differentiated with
  respect to the *looked-up rows* (not the tables), and the
  ``(ids, grad_rows)`` pairs feed the sharded lazy SGD — the update
  touches only the routed rows at shard shapes.

This is also the GC306 wiring point: with ``MXNET_TPU_PREFLIGHT=1`` the
first call compiles the step and runs
:func:`~mxnet_tpu.analysis.graphcheck.check_embedding_grad` over the
optimized HLO — a program that routes a lookup but still moves
full-table-sized gradient bytes through an all-reduce/all-gather (the
"you densified your embedding grad" footgun) gets a warning report in
the standard forensics dir before devices execute it.

Used by the 8-device dryrun compose check
(``__graft_entry__._sparse_embedding_check``) and
``tests/test_sparse_plane.py``.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .embedding import ShardedEmbedding, step_alltoall_model_bytes

__all__ = ["init_mlp", "make_recommender_step", "recommender_state",
           "lower_step"]


def init_mlp(dims: Sequence[int], seed: int = 0) -> Dict[str, jax.Array]:
    """Plain replicated MLP params {wI, bI}: the dense half of the DLRM
    interaction stack."""
    rs = np.random.RandomState(seed)
    out = {}
    for i in range(len(dims) - 1):
        fan_in = dims[i]
        out["w%d" % i] = jnp.asarray(
            (rs.randn(dims[i], dims[i + 1]) / np.sqrt(fan_in))
            .astype(np.float32))
        out["b%d" % i] = jnp.zeros((dims[i + 1],), jnp.float32)
    return out


def _mlp_apply(params: Dict[str, jax.Array], x):
    n = len(params) // 2
    for i in range(n):
        x = x @ params["w%d" % i] + params["b%d" % i]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def recommender_state(embs: Sequence[ShardedEmbedding], dense_dim: int,
                      hidden: Sequence[int] = (64, 32), seed: int = 0,
                      momentum: bool = True) -> dict:
    """Initial functional state: sharded tables (+ momentum slots, same
    sharding) and the replicated MLP (+ momentum)."""
    tables = tuple(e.init_state(seed=seed + i)
                   for i, e in enumerate(embs))
    moms = tuple(e.zeros_slot() if momentum else None for e in embs)
    in_dim = dense_dim + sum(e.dim for e in embs)
    mlp = init_mlp([in_dim] + list(hidden) + [1], seed=seed)
    mlp_mom = {k: jnp.zeros_like(v) for k, v in mlp.items()}
    return {"tables": tables, "moms": moms, "mlp": mlp,
            "mlp_mom": mlp_mom}


def make_recommender_step(embs: Sequence[ShardedEmbedding], lr: float = 0.05,
                          momentum: float = 0.9, wd: float = 0.0,
                          dp_axis: Optional[str] = None):
    """Build the jitted step: ``step(state, batch) -> (state, loss)``.

    ``batch``: ``{"ids": (F, B) int32, "dense": (B, Dd) f32,
    "label": (B,) f32}`` — B sharded over the embedding axis (= dp on
    the bench/dryrun meshes).  BCE loss on a sigmoid click head; MLP
    takes SGD+momentum (grads psum'd by GSPMD), each table takes the
    sharded lazy SGD over exactly the touched rows.
    """
    embs = list(embs)
    mesh = embs[0].mesh

    def loss_fn(mlp, emb_rows: Tuple, dense, label):
        x = jnp.concatenate(list(emb_rows) + [dense], axis=-1)
        logit = _mlp_apply(mlp, x)[:, 0]
        # numerically-stable sigmoid BCE
        loss = jnp.mean(jnp.maximum(logit, 0) - logit * label +
                        jnp.log1p(jnp.exp(-jnp.abs(logit))))
        return loss

    def step_fn(state, batch):
        ids = batch["ids"].astype(jnp.int32)
        emb_rows = tuple(
            e.lookup(t, ids[f])
            for f, (e, t) in enumerate(zip(embs, state["tables"])))
        loss, (g_mlp, g_rows) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(state["mlp"], emb_rows,
                                     batch["dense"], batch["label"])
        # dense half: SGD+momentum on the replicated MLP (GSPMD psums)
        mlp, mlp_mom = {}, {}
        for k, p in state["mlp"].items():
            g = g_mlp[k].astype(jnp.float32) + wd * p
            m = momentum * state["mlp_mom"][k] - lr * g
            mlp[k] = p + m
            mlp_mom[k] = m
        # sparse half: (ids, grad_rows) -> routed lazy update, touched
        # rows only, at shard shapes — the table-sized dense gradient
        # this path exists to avoid (GC306 polices the alternative)
        tables, moms = [], []
        for f, (e, t, mo) in enumerate(zip(embs, state["tables"],
                                           state["moms"])):
            t2, m2 = e.apply_sgd(t, mo, ids[f], g_rows[f], lr=lr,
                                 momentum=momentum, wd=wd)
            tables.append(t2)
            moms.append(m2)
        new_state = {"tables": tuple(tables), "moms": tuple(moms),
                     "mlp": mlp, "mlp_mom": mlp_mom}
        return new_state, loss

    # shardings ride the committed input arrays (tables device_put row-
    # sharded, MLP replicated, batch dp) — jit propagates them and the
    # shard_map routing inside constrains its own axis
    with mesh:
        jitted = jax.jit(step_fn)

    checked = [False]

    def step(state, batch):
        if not checked[0]:
            checked[0] = True
            _maybe_preflight(jitted, embs, state, batch)
        with mesh:
            new_state, loss = jitted(state, batch)
        from ..telemetry import memory as _memory
        if _memory.enabled():
            # the jitted update returns fresh buffers each step: keep
            # the tables attributable on the memory plane (the
            # ShardedTrainer re-tag discipline)
            for e, t, m in zip(embs, new_state["tables"],
                               new_state["moms"]):
                _memory.tag(t, "embedding", label=e.name)
                if m is not None:
                    _memory.tag(m, "embedding", label=e.name + ".slot")
            _memory.tag(new_state["mlp"], "params", label="recommender")
            _memory.tag(new_state["mlp_mom"], "optimizer",
                        label="recommender")
        return new_state, loss

    step.jitted = jitted
    step.embs = embs
    return step


def lower_step(step, state, batch):
    """Compiled HLO text of the recommender step for these shapes (the
    audit / GC306 entry: ``collective_accounting`` over it proves the
    all-to-all bytes match :func:`step_alltoall_model_bytes`)."""
    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype) \
            if hasattr(x, "shape") else x
    structs = jax.tree_util.tree_map(sds, (state, batch))
    return step.jitted.lower(*structs).compile().as_text()


def _maybe_preflight(jitted, embs, state, batch):
    """GC306 pre-flight (MXNET_TPU_PREFLIGHT=1): compile the step, scan
    the optimized HLO for table-sized dense gradient collectives, write
    the report into the standard forensics dir.  Degrades to a log line
    on any failure — preflight must never break a step."""
    from ..analysis import preflight as _preflight
    if not _preflight.enabled():
        return
    import logging
    try:
        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        structs = jax.tree_util.tree_map(sds, (state, batch))
        hlo = jitted.lower(*structs).compile().as_text()
        from ..analysis import graphcheck
        rep = graphcheck.check_embedding_grad(
            hlo, table_bytes=[e.table_bytes for e in embs],
            target="sparse.recommender_step")
        rep.extend(graphcheck.check_overlap(
            hlo, target="sparse.recommender_step"))
        _preflight.write_report(rep, "sparse", hlo_text=hlo)
        if rep.findings:
            logging.warning(
                "sparse preflight: %d finding(s) on the recommender "
                "step:\n%s", len(rep.findings),
                "\n".join("  [%s] %s" % (f.rule, f.message)
                          for f in rep.findings))
    except Exception:
        logging.exception("sparse preflight failed (continuing)")
