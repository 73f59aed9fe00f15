#!/usr/bin/env python3
"""Repo-wide footgun linter CLI (analysis engine 2, plus optional graph
checks) — the pre-merge gate for TPU-hostile patterns.

Usage:
    python tools/tpulint.py [paths...] [options]

    paths                 files/directories to lint (default: mxnet_tpu,
                          example and tools, relative to the repo root)
    --format pretty|json  output format (default pretty)
    --severity LEVEL      exit non-zero only on findings at/above LEVEL
                          (info|warning|error; default warning)
    --out FILE            also write the JSON report to FILE
    --graphcheck          additionally trace + check the built-in sharded
                          entry points (ShardedTrainer toy step, ring,
                          pipeline, moe) — needs jax and a few seconds
    --predict             compile the same entry points and print their
                          calibrated pre-flight budgets (predicted
                          step-time / peak-HBM / wire-bytes / throughput,
                          analysis/predict.py) as a table; each budget is
                          also written as an atomic predict-*.json into
                          the forensics dir and gated against the
                          MXNET_TPU_DEVICE_HBM_GB / _STEP_BUDGET_MS /
                          _WIRE_BUDGET_MB / _THROUGHPUT_FLOOR limits
                          (exit 1 when any budget is over)
    --max-findings N      cap pretty output (0 = all)

Exit status: 0 = clean at the gate severity, 1 = findings, 2 = usage/IO
error.  ``--format json`` emits ONE JSON document on stdout so CI can
both gate on the exit code and archive the findings.
"""
import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

DEFAULT_PATHS = ("mxnet_tpu", "example", "tools")


def _graphcheck_builtin(report):
    """Trace the repo's sharded entry points and fold the findings in —
    the 'lint the programs, not just the source' half of the CLI."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    import mxnet_tpu as mx
    from mxnet_tpu.analysis import graphcheck
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.parallel.ring import local_ring_attention_fn
    from mxnet_tpu.parallel import moe as moe_mod

    n = min(2, jax.device_count())
    mesh = make_mesh((n,), ("dp",))

    # ShardedTrainer toy step
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    trainer = ShardedTrainer(net, MeshSpec(mesh))
    shapes = {"data": (2 * n, 4), "softmax_label": (2 * n,)}
    params, mom, aux = trainer.init_state(shapes)
    inputs = {k: jax.ShapeDtypeStruct(v, jnp.float32)
              for k, v in shapes.items()}
    rep, _ = graphcheck.check_trainer(trainer, params, mom, aux, inputs)
    report.extend(rep)

    # ring attention block schedule
    ring_mesh = make_mesh((n,), ("sp",))
    fn = local_ring_attention_fn("sp", causal=True, scale=1.0,
                                 num_devices=n)
    mapped = shard_map(fn, mesh=ring_mesh,
                       in_specs=(P(None, "sp"),) * 3,
                       out_specs=P(None, "sp"))
    blk = jax.ShapeDtypeStruct((1, 2 * n, 2, 4), jnp.float32)
    report.extend(graphcheck.check_fn(mapped, blk, blk, blk,
                                      mesh=ring_mesh,
                                      target="parallel.ring_attention"))
    # GC304 needs compiled HLO (the -start/-done schedule): the ring toy
    # compiles in well under a second on the CPU mesh.  The 1 MB payload
    # floor keeps toy shapes from flagging; the rule's real teeth are the
    # seeded tests + the dryrun audit overlap line.
    try:
        txt = jax.jit(mapped).lower(blk, blk, blk).compile().as_text()
        report.extend(graphcheck.check_overlap(
            txt, target="parallel.ring_attention"))
    except Exception as e:      # compile envs vary; tracing already ran
        print("tpulint: ring overlap check skipped: %r" % e,
              file=sys.stderr)

    # moe dispatch/combine schedule
    ep_mesh = make_mesh((n,), ("ep",))
    local = moe_mod._moe_local_fn("ep", capacity=2,
                                  activation=jax.nn.relu)
    mapped = shard_map(local, mesh=ep_mesh,
                       in_specs=(P("ep"), P(), P("ep"), P("ep")),
                       out_specs=(P("ep"), P()))
    report.extend(graphcheck.check_fn(
        mapped,
        jax.ShapeDtypeStruct((4 * n, 8), jnp.float32),
        jax.ShapeDtypeStruct((8, n * 2), jnp.float32),
        jax.ShapeDtypeStruct((n * 2, 8, 16), jnp.float32),
        jax.ShapeDtypeStruct((n * 2, 16, 8), jnp.float32),
        mesh=ep_mesh, target="parallel.moe_ffn"))

    # pipeline tick schedule
    pp_mesh = make_mesh((n,), ("pp",))
    from mxnet_tpu.parallel.pipeline import pipeline_apply

    def check_pipeline():
        stacked = jax.ShapeDtypeStruct((n, 4), jnp.float32)
        x = jax.ShapeDtypeStruct((2, 1, 4), jnp.float32)

        def run(p, xm):
            return pipeline_apply(lambda pl, v: v * pl.sum(), n, pp_mesh,
                                  "pp", p, xm)
        report.extend(graphcheck.check_fn(
            run, stacked, x, mesh=pp_mesh,
            target="parallel.pipeline_apply"))
    check_pipeline()

    # sharded-embedding plane: routed lookup + lazy update must be GC306
    # clean (no table-sized dense gradient collective) — the compiled
    # HLO carries the collective payloads the rule reads
    try:
        from mxnet_tpu.sparse import ShardedEmbedding
        emb = ShardedEmbedding(16 * n, 8, MeshSpec(mesh), axis="dp",
                               name="tpulint")
        table = emb.init_state(seed=0)
        mom = emb.zeros_slot()
        ids = jax.device_put(
            jnp.arange(4 * n, dtype=jnp.int32) % (16 * n),
            jax.sharding.NamedSharding(mesh, P("dp")))

        def emb_step(t, m, i):
            rows = emb.lookup(t, i)
            return emb.apply_sgd(t, m, i, 2.0 * rows, lr=0.1,
                                 momentum=0.9)
        with mesh:
            txt = jax.jit(emb_step).lower(table, mom,
                                          ids).compile().as_text()
        report.extend(graphcheck.check_embedding_grad(
            txt, table_bytes=[emb.table_bytes],
            target="sparse.ShardedEmbedding"))
    except Exception as e:
        print("tpulint: sparse embedding check skipped: %r" % e,
              file=sys.stderr)

    # interactive decode step: the paged-KV step must trace identically
    # across token positions and batch membership (GC307 — the
    # recompile-per-token trap)
    try:
        from mxnet_tpu.serving.decode import (DecodeConfig, DecodeProgram,
                                              decode_retrace_report,
                                              init_decode_params)
        dcfg = DecodeConfig(32, 1, 16, 2, 16, page_size=4, max_seqs=2)
        dprog = DecodeProgram(init_decode_params(dcfg, seed=0), dcfg,
                              name="tpulint")
        report.extend(decode_retrace_report(dprog))
    except Exception as e:
        print("tpulint: decode retrace check skipped: %r" % e,
              file=sys.stderr)
    # async PS worker step: the dist_async contract is that the worker's
    # compute graph is collective-free — no peer in this rank's critical
    # path (GC106), plus the standard jaxpr rules
    try:
        from mxnet_tpu.kvstore.worker import TOY_DIM, make_worker_step
        wstep = make_worker_step(TOY_DIM)
        w = jax.ShapeDtypeStruct((TOY_DIM,), jnp.float32)
        x = jax.ShapeDtypeStruct((16, TOY_DIM), jnp.float32)
        y = jax.ShapeDtypeStruct((16,), jnp.float32)
        report.extend(graphcheck.check_fn(
            wstep, w, x, y, target="kvstore.worker_step"))
        report.extend(graphcheck.check_collective_free(
            wstep, w, x, y, target="kvstore.worker_step"))
    except Exception as e:
        print("tpulint: async worker check skipped: %r" % e,
              file=sys.stderr)

    # two-tier hierarchical all-reduce: the multi-pod schedule must pass
    # the axis/group rules on an island x dp mesh
    try:
        from mxnet_tpu.parallel import hierarchy
        ii = 2 if jax.device_count() >= 2 else 1
        kk = 2 if jax.device_count() >= 4 else 1
        hmesh = make_mesh((ii, kk), ("island", "dp"))

        def run_hier(st):
            return hierarchy.hierarchical_allreduce(st, hmesh)
        report.extend(graphcheck.check_fn(
            run_hier, jax.ShapeDtypeStruct((ii * kk, 8), jnp.float32),
            mesh=hmesh, target="parallel.hierarchical_allreduce"))
    except Exception as e:
        print("tpulint: hierarchical allreduce check skipped: %r" % e,
              file=sys.stderr)

    report.extend(graphcheck.check_registry())


def _predict_builtin():
    """Compile the standard entry points and emit their pre-flight
    budgets (ROADMAP item 1(a)): the same programs --graphcheck traces,
    run through analysis/predict.py's calibrated cost model.  Returns
    (reports, any_over_budget); an entry that fails to compile is
    skipped with a note on stderr, never fatal."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    import mxnet_tpu as mx
    from mxnet_tpu.analysis import predict
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.parallel.ring import local_ring_attention_fn
    from mxnet_tpu.parallel import moe as moe_mod

    n = min(2, jax.device_count())
    mesh = make_mesh((n,), ("dp",))
    store = predict.load_store()
    reports = []

    def run(tag, fn):
        try:
            reports.append(fn())
        except Exception as e:
            print("tpulint: --predict %s skipped: %r" % (tag, e),
                  file=sys.stderr)

    def trainer_budget():
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
        net = mx.sym.SoftmaxOutput(fc, name="softmax")
        trainer = ShardedTrainer(net, MeshSpec(mesh))
        shapes = {"data": (2 * n, 4), "softmax_label": (2 * n,)}
        params, mom, aux = trainer.init_state(shapes)
        inputs = {k: jax.ShapeDtypeStruct(v, jnp.float32)
                  for k, v in shapes.items()}
        jitted = trainer._step or trainer._build_step()
        compiled = jitted.lower(
            params, mom, aux, inputs, trainer._keys(),
            trainer._guard_arrays()).compile()
        rep = predict.predict_budget(compiled, "trainer", n_devices=n,
                                     mesh=mesh, items_per_step=2 * n,
                                     store=store)
        predict.save_report(rep)
        return rep

    def ring_budget():
        ring_mesh = make_mesh((n,), ("sp",))
        fn = local_ring_attention_fn("sp", causal=True, scale=1.0,
                                     num_devices=n)
        mapped = shard_map(fn, mesh=ring_mesh,
                           in_specs=(P(None, "sp"),) * 3,
                           out_specs=P(None, "sp"))
        blk = jax.ShapeDtypeStruct((1, 2 * n, 2, 4), jnp.float32)
        compiled = jax.jit(mapped).lower(blk, blk, blk).compile()
        rep = predict.predict_budget(compiled, "ring", n_devices=n,
                                     mesh=ring_mesh, store=store)
        predict.save_report(rep)
        return rep

    def moe_budget():
        ep_mesh = make_mesh((n,), ("ep",))
        local = moe_mod._moe_local_fn("ep", capacity=2,
                                      activation=jax.nn.relu)
        mapped = shard_map(local, mesh=ep_mesh,
                           in_specs=(P("ep"), P(), P("ep"), P("ep")),
                           out_specs=(P("ep"), P()))
        compiled = jax.jit(mapped).lower(
            jax.ShapeDtypeStruct((4 * n, 8), jnp.float32),
            jax.ShapeDtypeStruct((8, n * 2), jnp.float32),
            jax.ShapeDtypeStruct((n * 2, 8, 16), jnp.float32),
            jax.ShapeDtypeStruct((n * 2, 16, 8), jnp.float32)).compile()
        rep = predict.predict_budget(compiled, "moe", n_devices=n,
                                     mesh=ep_mesh,
                                     items_per_step=4 * n, store=store)
        predict.save_report(rep)
        return rep

    def pipeline_budget():
        from mxnet_tpu.parallel.pipeline import pipeline_apply
        pp_mesh = make_mesh((n,), ("pp",))
        stacked = jax.ShapeDtypeStruct((n, 4), jnp.float32)
        x = jax.ShapeDtypeStruct((2, 1, 4), jnp.float32)

        def run_pp(p, xm):
            return pipeline_apply(lambda pl, v: v * pl.sum(), n, pp_mesh,
                                  "pp", p, xm)
        compiled = jax.jit(run_pp).lower(stacked, x).compile()
        rep = predict.predict_budget(compiled, "pipeline", n_devices=n,
                                     mesh=pp_mesh, store=store)
        predict.save_report(rep)
        return rep

    def recommender_budget():
        from mxnet_tpu.sparse import ShardedEmbedding
        emb = ShardedEmbedding(16 * n, 8, MeshSpec(mesh), axis="dp",
                               name="tpulint_predict")
        table = emb.init_state(seed=0)
        mom = emb.zeros_slot()
        ids = jax.device_put(
            jnp.arange(4 * n, dtype=jnp.int32) % (16 * n),
            jax.sharding.NamedSharding(mesh, P("dp")))

        def emb_step(t, m, i):
            rows = emb.lookup(t, i)
            return emb.apply_sgd(t, m, i, 2.0 * rows, lr=0.1,
                                 momentum=0.9)
        with mesh:
            compiled = jax.jit(emb_step).lower(table, mom, ids).compile()
        rep = predict.predict_budget(compiled, "recommender",
                                     n_devices=n, mesh=mesh,
                                     items_per_step=4 * n, store=store)
        predict.save_report(rep)
        return rep

    def decode_budget():
        from mxnet_tpu.serving.decode import DecodeConfig
        dcfg = DecodeConfig(32, 1, 16, 2, 16, page_size=4, max_seqs=2)
        rep = predict.predict_decode_budget(
            dcfg.num_layers, dcfg.hidden, dcfg.vocab_size, dcfg.max_seqs,
            dcfg.max_seq_len, name="decode", store=store)
        predict.save_report(rep)
        return rep

    run("trainer", trainer_budget)
    run("ring", ring_budget)
    run("moe", moe_budget)
    run("pipeline", pipeline_budget)
    run("recommender", recommender_budget)
    run("decode", decode_budget)
    over = any(r.get("over_budget") for r in reports)
    return reports, over


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*", help="files/dirs to lint")
    ap.add_argument("--format", choices=("pretty", "json"),
                    default="pretty")
    ap.add_argument("--severity", choices=("info", "warning", "error"),
                    default="warning",
                    help="exit-1 gate: findings at/above this level")
    ap.add_argument("--out", help="also write JSON report here")
    ap.add_argument("--graphcheck", action="store_true",
                    help="also trace+check built-in sharded entry points")
    ap.add_argument("--predict", action="store_true",
                    help="also print calibrated pre-flight budgets for "
                         "the built-in entry points (exit 1 when over "
                         "budget)")
    ap.add_argument("--max-findings", type=int, default=0)
    args = ap.parse_args(argv)

    paths = args.paths or [os.path.join(_REPO, p) for p in DEFAULT_PATHS]
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print("tpulint: no such path(s): %s" % ", ".join(missing),
              file=sys.stderr)
        return 2

    from mxnet_tpu.analysis import srclint
    report = srclint.lint_paths(paths)
    report.engine = "tpulint"
    if args.graphcheck:
        try:
            _graphcheck_builtin(report)
        except Exception as e:                      # noqa: BLE001
            print("tpulint: --graphcheck failed: %r" % e, file=sys.stderr)
            return 2

    over_budget = False
    predict_reports = []
    if args.predict:
        try:
            from mxnet_tpu.analysis import predict as predict_mod
            predict_reports, over_budget = _predict_builtin()
        except Exception as e:                      # noqa: BLE001
            print("tpulint: --predict failed: %r" % e, file=sys.stderr)
            return 2

    if args.out:
        report.save(args.out)
    if args.format == "json":
        doc = json.loads(report.to_json())
        if args.predict:
            doc["predict"] = predict_reports
        print(json.dumps(doc, indent=2, default=repr))
    else:
        print(report.pretty(max_findings=args.max_findings))
        if args.predict:
            print(predict_mod.budget_table(predict_reports))

    gated = report.at_or_above(args.severity)
    return 1 if (gated or over_budget) else 0


if __name__ == "__main__":
    sys.exit(main())
