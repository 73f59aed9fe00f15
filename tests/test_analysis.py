"""Static-analysis subsystem tests (ISSUE 3): graphcheck jaxpr rules,
srclint fixture coverage, pre-flight wiring, CLI gating, and the repo
self-lint that keeps the shipped tree at zero gate-severity findings.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

import mxnet_tpu as mx
from mxnet_tpu.analysis import (Finding, PreflightError, Report, graphcheck,
                                preflight, srclint)
from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")

def _mesh(n=2, axis="dp"):
    return make_mesh((n,), (axis,))


def _smap(fn, mesh, in_specs, out_specs):
    # check_vma off: these programs are seeded with divergent collectives
    # on purpose, and graphcheck — not the tracer — must be what flags them
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _rules(report):
    return sorted({f.rule for f in report})


# ---------------------------------------------------------------------------
# report model
# ---------------------------------------------------------------------------

def test_report_model_roundtrip(tmp_path):
    rep = Report("graphcheck", "unit")
    rep.add("GC102", "error", "boom", location="x:1", fix_hint="fix it")
    rep.add("GC201", "warning", "meh")
    rep.add("GC000", "info", "fyi")
    assert len(rep.errors()) == 1 and len(rep.warnings()) == 1
    assert [f.rule for f in rep.sorted()][0] == "GC102"
    assert len(rep.at_or_above("warning")) == 2
    path = rep.save(str(tmp_path / "r.json"))
    back = Report.load(path)
    assert back.counts() == rep.counts()
    assert back.findings[0].fix_hint == "fix it"
    text = rep.pretty()
    assert "GC102" in text and "ERROR" in text


def test_report_rejects_unknown_severity():
    with pytest.raises(ValueError):
        Finding("X", "fatal", "nope")


# ---------------------------------------------------------------------------
# graphcheck: collective-schedule extraction
# ---------------------------------------------------------------------------

def test_collect_collectives_scan_cond_nesting():
    mesh = _mesh()

    def nested(x):
        def body(c, t):
            c = lax.ppermute(c, "dp", [(0, 1), (1, 0)])
            c = lax.cond(t > 0,
                         lambda v: lax.psum(v, "dp"),
                         lambda v: lax.psum(v, "dp"), c)
            return c, t

        c, _ = lax.scan(body, x, jnp.arange(3))
        return c

    closed = jax.make_jaxpr(_smap(nested, mesh, P("dp"), P("dp")))(
        jnp.ones((4, 2)))
    events = graphcheck.collect_collectives(closed)
    assert [e.prim for e in events] == ["ppermute", "psum", "psum"]
    assert all(e.axes == ("dp",) for e in events)
    # paths name the nesting: shard_map -> scan body -> cond branches
    assert "scan" in events[0].path
    assert "branches[0]" in events[1].path
    assert "branches[1]" in events[2].path
    # symmetric cond: no divergence findings
    rep = graphcheck.check_jaxpr(closed, mesh=mesh)
    assert rep.errors() == []


def test_cond_divergent_schedule_is_flagged():
    """Acceptance criterion: the chaos-'hang'-style asymmetric program —
    a collective only SOME ranks reach — is rejected statically, where
    PR-2's watchdog could only catch the resulting live hang."""
    mesh = _mesh()

    def asymmetric(x):
        # data-dependent predicate: ranks can disagree, and then the
        # psum-taking branch blocks forever waiting for the others
        return lax.cond(x.sum() > 0,
                        lambda v: lax.psum(v, "dp"),
                        lambda v: v, x)

    rep = graphcheck.check_fn(_smap(asymmetric, mesh, P("dp"), P("dp")),
                              jnp.ones((4, 2)), mesh=mesh)
    errs = [f for f in rep.errors() if f.rule == "GC102"]
    assert len(errs) == 1
    assert "deadlock" in errs[0].message


def test_axis_name_mismatch_flagged():
    mesh = _mesh()

    def f(x):
        return lax.psum(x, "dp")

    closed = jax.make_jaxpr(_smap(f, mesh, P("dp"), P("dp")))(jnp.ones(4))
    # the program reduces over 'dp' but the deployment mesh only has 'tp'
    rep = graphcheck.check_jaxpr(closed, mesh={"tp": 2})
    assert [f.rule for f in rep.errors()] == ["GC101"]
    # and is clean against its own mesh
    assert graphcheck.check_jaxpr(closed, mesh=mesh).errors() == []


def test_ppermute_bad_perm_flagged():
    mesh = _mesh()

    def bad(x):
        return lax.ppermute(x, "dp", [(0, 0), (1, 0)])

    rep = graphcheck.check_fn(_smap(bad, mesh, P("dp"), P("dp")),
                              jnp.ones(4), mesh=mesh)
    assert [f.rule for f in rep.errors()] == ["GC104"]

    def good(x):
        return lax.ppermute(x, "dp", [(0, 1), (1, 0)])

    rep2 = graphcheck.check_fn(_smap(good, mesh, P("dp"), P("dp")),
                               jnp.ones(4), mesh=mesh)
    assert rep2.errors() == []


def test_ppermute_rank_out_of_range_flagged():
    mesh = _mesh()

    def oob(x):
        return lax.ppermute(x, "dp", [(0, 1), (1, 3)])

    rep = graphcheck.check_fn(_smap(oob, mesh, P("dp"), P("dp")),
                              jnp.ones(4), mesh=mesh)
    assert any(f.rule == "GC104" and "outside axis" in f.message
               for f in rep.errors())


def test_axis_groups_asymmetric_flagged():
    mesh = _mesh(4)

    def grouped(x):
        return lax.psum(x, "dp", axis_index_groups=[[0, 1], [2]])

    rep = graphcheck.check_fn(_smap(grouped, mesh, P("dp"), P("dp")),
                              jnp.ones(8), mesh=mesh)
    assert any(f.rule == "GC105" for f in rep.errors())


def test_while_loop_collective_warns():
    mesh = _mesh()

    def w(x):
        return lax.while_loop(lambda c: c.sum() < 10,
                              lambda c: lax.psum(c, "dp") + 1, x)

    rep = graphcheck.check_fn(_smap(w, mesh, P("dp"), P("dp")),
                              jnp.ones(4), mesh=mesh)
    assert [f.rule for f in rep.warnings()] == ["GC103"]
    assert rep.errors() == []


# ---------------------------------------------------------------------------
# graphcheck: dtype / sharding / recompile rules
# ---------------------------------------------------------------------------

def test_bf16_upcast_into_dot_flagged():
    def up(x):
        y = x.astype(jnp.float32)
        return y @ y.T

    rep = graphcheck.check_fn(up, jnp.ones((4, 4), jnp.bfloat16))
    assert any(f.rule == "GC301" for f in rep.warnings())

    def accum(x):
        # the INTENDED pattern: bf16 operands, f32 accumulation
        return jax.lax.dot(x, x.T, precision=None,
                           preferred_element_type=jnp.float32)

    rep2 = graphcheck.check_fn(accum, jnp.ones((4, 4), jnp.bfloat16))
    assert not any(f.rule == "GC301" for f in rep2)


def test_weak_type_input_flagged():
    rep = graphcheck.check_fn(lambda s, x: x * s, 1.0, jnp.ones(3))
    assert any(f.rule == "GC302" for f in rep.warnings())
    rep2 = graphcheck.check_fn(lambda s, x: x * s,
                               jnp.asarray(1.0, jnp.float32), jnp.ones(3))
    assert not any(f.rule == "GC302" for f in rep2)


def test_reshard_chain_flagged():
    mesh = _mesh()

    def rs(x):
        y = lax.with_sharding_constraint(x, NamedSharding(mesh, P("dp")))
        return lax.with_sharding_constraint(y, NamedSharding(mesh, P(None)))

    rep = graphcheck.check_fn(rs, jnp.ones(4))
    assert any(f.rule == "GC203" for f in rep.warnings())


def test_check_model_axis_replication_flags_large_replicated_on_model_axis():
    mesh = make_mesh((2, 2), ("dp", "tp")) if jax.device_count() >= 4 \
        else make_mesh((1, 2), ("dp", "tp"))
    big = (2048, 2048)          # 16 MB f32 > default 8 MB threshold
    entries = [
        ("big_replicated", big, 4, NamedSharding(mesh, P())),
        ("big_sharded", big, 4, NamedSharding(mesh, P("tp", None))),
        ("small_replicated", (8, 8), 4, NamedSharding(mesh, P())),
    ]
    rep = graphcheck.check_model_axis_replication(entries, mesh, model_axes=("tp",))
    assert [f.location for f in rep.warnings()] == ["big_replicated"]
    # pure-dp mesh: replication is the design, nothing fires
    rep2 = graphcheck.check_model_axis_replication(entries, _mesh(), model_axes=())
    assert len(rep2) == 0


def test_check_donation():
    assert len(graphcheck.check_donation(True, "step")) == 0
    rep = graphcheck.check_donation(False, "step")
    assert [f.rule for f in rep.warnings()] == ["GC202"]


def test_check_registry_clean_and_seeded_gap():
    from mxnet_tpu.base import Param
    from mxnet_tpu.ops import registry as reg
    # the shipped registry is clean — every per-step param is dynamic
    assert len(graphcheck.check_registry()) == 0
    # seed a gap: an optimizer-style op whose lr is a static jit key
    name = "_ta_bad_update"

    @reg.register(name, inputs=("weight", "grad"),
                  params=dict(lr=Param(float, 0.1)))
    def _bad_update(attrs, w, g):
        return w - attrs.lr * g

    try:
        rep = graphcheck.check_registry()
        assert any(f.rule == "GC402" and name in f.message
                   for f in rep.warnings())
    finally:
        reg._REGISTRY.pop(name)


def test_check_symbol_static_float_attr_seeded():
    from mxnet_tpu.base import Param
    from mxnet_tpu.ops import registry as reg
    name = "_ta_bad_symop"

    @reg.register(name, inputs=("data",),
                  params=dict(lr=Param(float, 0.1)))
    def _bad_symop(attrs, x):
        return x * attrs.lr

    try:
        v = mx.sym.Variable("data")
        s = mx.sym.create(name, [v], {"lr": 0.05, "name": "badnode"})
        rep = graphcheck.check_symbol(s)
        assert any(f.rule == "GC401" for f in rep.warnings())
        # the shipped optimizer ops keep lr dynamic -> clean
        w = mx.sym.Variable("w")
        g = mx.sym.Variable("g")
        ok = mx.sym.create("sgd_update", [w, g], {"lr": 0.05})
        assert len(graphcheck.check_symbol(ok)) == 0
    finally:
        reg._REGISTRY.pop(name)


# ---------------------------------------------------------------------------
# pre-flight wiring
# ---------------------------------------------------------------------------

def _toy_trainer(n_dev=2):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    spec = MeshSpec(_mesh(n_dev))
    trainer = ShardedTrainer(net, spec, lr=0.1)
    shapes = {"data": (8, 32), "softmax_label": (8,)}
    return trainer, trainer.init_state(shapes)


def test_trainer_preflight_writes_report_and_passes(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT", "1")
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT_DIR", str(tmp_path))
    trainer, (params, mom, aux) = _toy_trainer()
    batch = {"data": np.random.rand(8, 32).astype(np.float32),
             "softmax_label": np.zeros(8, np.float32)}
    params, mom, aux, loss = trainer.step(params, mom, aux, batch)
    assert np.isfinite(float(loss))
    reports = [p for p in os.listdir(str(tmp_path))
               if p.startswith("preflight-trainer") and p.endswith(".json")]
    assert len(reports) == 1
    rep = Report.load(str(tmp_path / reports[0]))
    assert rep.errors() == []          # the shipped step program is clean
    assert "jaxpr" in rep.artifacts
    assert os.path.isfile(rep.artifacts["jaxpr"])
    # preflight runs ONCE per trainer
    trainer.step(params, mom, aux, batch)
    assert len([p for p in os.listdir(str(tmp_path))
                if p.endswith(".json")]) == 1


def test_trainer_preflight_off_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_TPU_PREFLIGHT", raising=False)
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT_DIR", str(tmp_path))
    trainer, (params, mom, aux) = _toy_trainer()
    batch = {"data": np.zeros((8, 32), np.float32),
             "softmax_label": np.zeros(8, np.float32)}
    trainer.step(params, mom, aux, batch)
    assert os.listdir(str(tmp_path)) == []


def test_module_preflight_writes_report(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT", "1")
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT_DIR", str(tmp_path))
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    from mxnet_tpu.module import Module
    mod = Module(net, context=[mx.cpu()])
    mod.bind(data_shapes=[("data", (4, 16))],
             label_shapes=[("softmax_label", (4,))])
    reports = [p for p in os.listdir(str(tmp_path))
               if p.startswith("preflight-module") and p.endswith(".json")]
    assert len(reports) == 1
    assert Report.load(str(tmp_path / reports[0])).errors() == []


def test_preflight_aborts_on_error_findings(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT_DIR", str(tmp_path))
    monkeypatch.delenv("MXNET_TPU_PREFLIGHT_ACTION", raising=False)
    bad = Report("graphcheck", "seeded")
    bad.add("GC102", "error", "divergent schedule")
    with pytest.raises(PreflightError) as ei:
        preflight._finish(bad, "seeded")
    assert "GC102" in str(ei.value)
    assert ei.value.report is bad
    # the report is persisted even though we aborted
    assert any(p.endswith(".json") for p in os.listdir(str(tmp_path)))
    # action=warn downgrades to logging
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT_ACTION", "warn")
    preflight._finish(bad, "seeded2")


def test_preflight_catches_seeded_divergence_end_to_end(tmp_path,
                                                        monkeypatch):
    """Full loop: an asymmetric program goes through the same
    check+report+abort path the trainer pre-flight uses."""
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT_DIR", str(tmp_path))
    mesh = _mesh()

    def asymmetric(x):
        return lax.cond(x.sum() > 0,
                        lambda v: lax.psum(v, "dp"),
                        lambda v: v, x)

    rep = graphcheck.check_fn(_smap(asymmetric, mesh, P("dp"), P("dp")),
                              jnp.ones((4, 2)), mesh=mesh,
                              target="seeded-hang")
    with pytest.raises(PreflightError):
        preflight._finish(rep, "seeded-hang")


# ---------------------------------------------------------------------------
# srclint
# ---------------------------------------------------------------------------

def test_srclint_fixture_catches_every_rule():
    rep = srclint.lint_file(os.path.join(FIXTURES,
                                         "srclint_violations.py"),
                            in_library=False)
    by_rule = {}
    for f in rep:
        by_rule.setdefault(f.rule, []).append(f)
    assert set(by_rule) == {"SL101", "SL102", "SL103", "SL104", "SL105"}
    assert len(by_rule["SL101"]) == 2      # decorator + combinator paths
    assert len(by_rule["SL102"]) == 2      # decorator + collective-body
    assert len(by_rule["SL103"]) == 2      # .get + subscript
    assert len(by_rule["SL104"]) == 2      # random + np.random
    assert len(by_rule["SL105"]) == 1
    # the suppressed lambda produced nothing (checked by exact counts)


def test_srclint_library_rule_sl106():
    rep = srclint.lint_file(
        os.path.join(FIXTURES, "srclint_library_violations.py"),
        in_library=True)
    assert [f.rule for f in rep] == ["SL106"]
    assert rep.findings[0].extra["function"] == "unarmed_entry"
    # outside the library the rule stays quiet
    rep2 = srclint.lint_file(
        os.path.join(FIXTURES, "srclint_library_violations.py"),
        in_library=False)
    assert len(rep2) == 0


def test_srclint_sl107_manual_timing_in_library():
    """SL107 (info): a host-side library function hand-rolling start/stop
    timing should use a telemetry span; deadline arithmetic and
    span-based timing stay quiet."""
    src = (
        "import time\n"
        "from mxnet_tpu import telemetry\n"
        "def hand_rolled(work):\n"
        "    t0 = time.perf_counter()\n"
        "    work()\n"
        "    return time.perf_counter() - t0\n"
        "def deadline_math(budget):\n"
        "    deadline = time.monotonic() + budget\n"
        "    while time.monotonic() < deadline:\n"
        "        pass\n"
        "    return deadline - budget\n"
        "def span_based(work):\n"
        "    with telemetry.span('x', timed=True) as sp:\n"
        "        work()\n"
        "    return sp.duration\n"
    )
    rep = srclint.lint_source(src, "mxnet_tpu/inline_lib.py",
                              in_library=True)
    assert [f.rule for f in rep] == ["SL107"]
    assert rep.findings[0].extra["function"] == "hand_rolled"
    assert rep.findings[0].severity == "info"
    # host-only: app/tools code outside the library is not flagged
    assert len(srclint.lint_source(src, "tools/inline_app.py",
                                   in_library=False)) == 0
    # the instrumentation layer itself is exempt
    assert len(srclint.lint_source(
        src, "mxnet_tpu/telemetry/inline.py", in_library=True)) == 0
    # a TRACED function with the same pattern is SL102's territory
    traced = (
        "import time, jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    t0 = time.perf_counter()\n"
        "    return x * (time.perf_counter() - t0)\n"
    )
    rep2 = srclint.lint_source(traced, "mxnet_tpu/inline2.py",
                               in_library=True)
    assert set(f.rule for f in rep2) == {"SL102"}


def test_srclint_suppression_scopes():
    src = (
        "import time, jax\n"
        "@jax.jit\n"
        "def f(x):  # tpulint: disable=SL102\n"
        "    return x + time.time()\n"
        "@jax.jit\n"
        "def g(x):\n"
        "    return x + time.time()  # tpulint: disable=all\n"
        "@jax.jit\n"
        "def h(x):\n"
        "    return x + time.time()\n"
    )
    rep = srclint.lint_source(src, "inline.py")
    assert [f.extra["function"] for f in rep] == ["h"]
    filewide = "# tpulint: disable-file=SL102\n" + src
    assert len(srclint.lint_source(filewide, "inline2.py")) == 0


def test_srclint_sl108_sync_iter_fixture():
    """SL108 (warning): training loops iterating a synchronous DataIter
    directly are flagged; prefetch-wrapped, eval-only, and suppressed
    loops stay quiet."""
    rep = srclint.lint_file(os.path.join(FIXTURES, "srclint_sync_iter.py"),
                            in_library=False)
    assert [f.rule for f in rep] == ["SL108", "SL108"]
    assert sorted(f.extra["function"] for f in rep) == [
        "bad_module_loop", "bad_trainer_loop"]
    assert all(f.severity == "warning" for f in rep)
    assert "PrefetchingIter" in rep.findings[0].fix_hint


def test_srclint_sl108_module_scope_and_wrapping():
    """SL108 fires at module scope too, and any rebind through
    PrefetchingIter — even under a different name — clears the var."""
    src = (
        "from mxnet_tpu.io import NDArrayIter, PrefetchingIter\n"
        "it = NDArrayIter(x, y, batch_size=4)\n"
        "for batch in it:\n"
        "    trainer.step(state, batch)\n"
    )
    rep = srclint.lint_source(src, "inline_sync.py")
    assert [f.rule for f in rep] == ["SL108"]
    assert not rep.findings[0].extra.get("function")   # module scope
    wrapped = (
        "from mxnet_tpu.io import NDArrayIter, PrefetchingIter\n"
        "raw = NDArrayIter(x, y, batch_size=4)\n"
        "it = PrefetchingIter(raw)\n"
        "for batch in raw:\n"
        "    trainer.step(state, batch)\n"
    )
    # the raw handle was consumed by a prefetch wrapper: don't double-flag
    assert len(srclint.lint_source(wrapped, "inline_wrapped.py")) == 0


def test_srclint_host_helpers_not_false_flagged():
    """A helper CALLED from a traced fn runs at trace time with static
    args: np-on-param must not fire (SL101), but frozen clocks must
    (SL102)."""
    src = (
        "import time\n"
        "import numpy as np\n"
        "import jax\n"
        "def shape_helper(shape):\n"
        "    return int(np.prod(shape))\n"
        "def clock_helper():\n"
        "    return time.time()\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    n = shape_helper(x.shape)\n"
        "    return x.reshape(n) + clock_helper()\n"
    )
    rep = srclint.lint_source(src, "inline3.py")
    assert [f.rule for f in rep] == ["SL102"]
    assert rep.findings[0].extra["function"] == "clock_helper"


def test_repo_self_lint_zero_gate_findings():
    """The shipped tree must stay clean at the CI gate severity
    (warning+): new ERROR findings fail this test outright, and any new
    warning needs an explicit suppression with a justification."""
    rep = srclint.lint_paths([os.path.join(REPO, "mxnet_tpu"),
                              os.path.join(REPO, "example"),
                              os.path.join(REPO, "tools")])
    gated = rep.at_or_above("warning")
    assert gated == [], "repo self-lint regressions:\n%s" % "\n".join(
        "%s %s %s: %s" % (f.severity.upper(), f.rule, f.location,
                          f.message) for f in gated)


def test_repo_graphcheck_entry_points_clean():
    """Graph-level self-lint: the trainer step program traces clean."""
    trainer, (params, mom, aux) = _toy_trainer()
    inputs = {"data": jax.ShapeDtypeStruct((8, 32), jnp.float32),
              "softmax_label": jax.ShapeDtypeStruct((8,), jnp.float32)}
    rep, closed = graphcheck.check_trainer(trainer, params, mom, aux,
                                           inputs)
    assert rep.errors() == [], [f.message for f in rep.errors()]
    # the trace is real: the step program contains eqns
    assert len(closed.jaxpr.eqns) > 0


# ---------------------------------------------------------------------------
# CLI + hlo_diff integration
# ---------------------------------------------------------------------------

def test_tpulint_cli_json_gates_on_findings(tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import tpulint
    finally:
        sys.path.pop(0)
    out = str(tmp_path / "report.json")
    rc = tpulint.main([os.path.join(FIXTURES, "srclint_violations.py"),
                       "--format", "json", "--out", out])
    assert rc == 1
    data = json.loads(capsys.readouterr().out)
    assert data["counts"]["error"] >= 5
    assert os.path.isfile(out)
    # gate at error-severity only: fixture still fails (it has errors)
    assert tpulint.main([os.path.join(FIXTURES, "srclint_violations.py"),
                         "--format", "json", "--severity", "error"]) == 1
    capsys.readouterr()
    # the shipped tree passes the default gate
    rc_clean = tpulint.main([os.path.join(REPO, "mxnet_tpu"),
                             os.path.join(REPO, "example"),
                             "--format", "json"])
    capsys.readouterr()
    assert rc_clean == 0


def test_tpulint_predict_self_run(tmp_path, capsys, monkeypatch):
    """``tpulint --predict`` compiles the built-in entry points, prints a
    budget for every one, writes predict-*.json artifacts, and stays
    clean (rc 0) over a lint-clean target."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import tpulint
    finally:
        sys.path.pop(0)
    monkeypatch.setenv("MXNET_TPU_CALIBRATION_CACHE",
                       str(tmp_path / "calibration.json"))
    monkeypatch.setenv("MXNET_TPU_ATTRIBUTION_DIR", str(tmp_path / "rep"))
    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    rc = tpulint.main(["--predict", str(clean), "--format", "json"])
    out = capsys.readouterr().out
    assert rc == 0, out
    doc = json.loads(out)
    programs = {r["program"] for r in doc["predict"]}
    assert {"trainer", "ring", "moe", "pipeline", "recommender",
            "decode"} <= programs
    for r in doc["predict"]:
        assert r["budget"]["step_time_s"] > 0
        assert r["budget"]["peak_hbm_bytes"] > 0
        assert r["basis"]["achievable_fraction"] > 0
        assert not r["over_budget"]
    written = [f for f in os.listdir(str(tmp_path / "rep"))
               if f.startswith("predict-")]
    assert len(written) >= 6


def test_hlo_diff_from_saved_graphcheck_findings(tmp_path, capsys, monkeypatch):
    hlo_a = tmp_path / "a.hlo.txt"
    hlo_a.write_text(
        "  %x = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)\n"
        "  %y = f32[4]{0} all-reduce(f32[4]{0} %x)\n")
    hlo_b = tmp_path / "b.hlo.txt"
    hlo_b.write_text("  %x = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)\n")
    rep = Report("graphcheck", "unit")
    rep.artifacts["hlo"] = str(hlo_a)
    rep_path = rep.save(str(tmp_path / "rep.json"))

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import hlo_diff
    finally:
        sys.path.pop(0)
    monkeypatch.setattr(sys, "argv",
                        ["hlo_diff.py", "--from-graphcheck", rep_path,
                         "--against", str(hlo_b)])
    hlo_diff.main()
    out = capsys.readouterr().out
    assert "all-reduce" in out and "+1" in out
    # single-report mode prints the histogram
    monkeypatch.setattr(sys, "argv",
                        ["hlo_diff.py", "--from-graphcheck", rep_path])
    hlo_diff.main()
    assert "all-reduce" in capsys.readouterr().out
    # a report without an HLO artifact explains the knob
    bare = Report("graphcheck", "unit2").save(str(tmp_path / "bare.json"))
    monkeypatch.setattr(sys, "argv",
                        ["hlo_diff.py", "--from-graphcheck", bare])
    with pytest.raises(SystemExit) as ei:
        hlo_diff.main()
    assert "MXNET_TPU_PREFLIGHT_HLO" in str(ei.value)


# ---------------------------------------------------------------------------
# satellite regressions: the true positives the analyzer surfaced
# ---------------------------------------------------------------------------

def test_fused_sgd_momentum_buffers_are_donated():
    """GC202 true positive: the fused SGD whole-step update now donates
    the momentum buffers (update_batch rebinds them immediately), so the
    update no longer holds old+new momentum for the whole model live."""
    from mxnet_tpu.optimizer import _fused_sgd_program
    run = _fused_sgd_program(momentum_on=True, clip=0.0)
    ws = (jnp.ones(4),)
    gs = (jnp.ones(4),)
    ms = (jnp.zeros(4),)
    low = run.lower(ws, gs, ms, (0.1,), (0.0,), 1.0, 0.9).as_text()
    assert "tf.aliasing_output" in low, \
        "momentum donation regressed (GC202)"
    # math unchanged: one step of sgd_mom
    new_ws, new_ms = run(ws, gs, ms, (0.1,), (0.0,), 1.0, 0.9)
    np.testing.assert_allclose(np.asarray(new_ms[0]), -0.1 * np.ones(4),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_ws[0]), 0.9 * np.ones(4),
                               rtol=1e-6)


@pytest.mark.skipif(jax.device_count() < 2, reason="needs 2 devices")
def test_audit_trail_covers_every_collective_kind():
    """Audit-trail true positive: pipeline/moe record EVERY collective
    kind their traced schedule contains (graphcheck extraction is the
    oracle), so a hang post-mortem's 'last completed collective' cannot
    name a kind the program never finished."""
    from mxnet_tpu.parallel import audit
    from mxnet_tpu.parallel.pipeline import pipeline_apply

    audit.clear_collective_log()
    mesh = _mesh(2, "pp")
    params = jnp.stack([jnp.ones(3), 2 * jnp.ones(3)])
    x = jnp.ones((2, 1, 3))
    pipeline_apply(lambda p, v: v * p.sum(), 2, mesh, "pp", params, x)
    kinds = {e["kind"] for e in audit.collective_log()
             if "pipeline" in e["tag"]}
    assert kinds == {"collective-permute", "all-reduce"}

    audit.clear_collective_log()
    from mxnet_tpu.parallel.moe import moe_ffn
    ep = _mesh(2, "ep")
    T, d, E, h = 8, 4, 2, 8
    rng = np.random.RandomState(0)
    out, aux_loss = moe_ffn(
        jnp.asarray(rng.randn(T, d), jnp.float32),
        jnp.asarray(rng.randn(d, E), jnp.float32),
        jnp.asarray(rng.randn(E, d, h), jnp.float32),
        jnp.asarray(rng.randn(E, h, d), jnp.float32), ep)
    kinds = {e["kind"] for e in audit.collective_log()
             if "moe" in e["tag"]}
    assert kinds == {"all-to-all", "all-reduce"}


# ---------------------------------------------------------------------------
# GC501: pre-flight HBM capacity (the memory plane's graphcheck rule)
# ---------------------------------------------------------------------------

def test_gc501_capacity_exceeded_flagged():
    rep = graphcheck.check_capacity(32e9, capacity_bytes=16e9,
                                    target="seeded")
    assert _rules(rep) == ["GC501"]
    (f,) = rep.errors()
    assert "32.00 GB" in f.message and "16.00 GB" in f.message
    assert f.extra["predicted_bytes"] == 32_000_000_000


def test_gc501_clean_under_capacity_and_unknown_capacity(monkeypatch):
    assert len(graphcheck.check_capacity(8e9, capacity_bytes=16e9)) == 0
    # unknown capacity (CPU dev box, no env override): rule disables
    monkeypatch.delenv("MXNET_TPU_DEVICE_HBM_GB", raising=False)
    assert len(graphcheck.check_capacity(1e18)) == 0
    # env override supplies the capacity where the backend reports none
    monkeypatch.setenv("MXNET_TPU_DEVICE_HBM_GB", "16")
    from mxnet_tpu.telemetry import memory as _memory
    assert _memory.device_capacity_bytes() == 16e9
    assert _rules(graphcheck.check_capacity(32e9)) == ["GC501"]


def test_gc501_trainer_preflight_seeded_and_clean(tmp_path, monkeypatch):
    """End-to-end: a trainer whose state+batch cannot fit the (tiny,
    env-seeded) capacity is refused BEFORE dispatch with a GC501 ERROR;
    with a sane capacity the same trainer passes."""
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT", "1")
    monkeypatch.setenv("MXNET_TPU_PREFLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_DEVICE_HBM_GB", "0.000001")  # 1 kB
    trainer, (params, mom, aux) = _toy_trainer()
    batch = {"data": np.zeros((8, 32), np.float32),
             "softmax_label": np.zeros(8, np.float32)}
    with pytest.raises(PreflightError) as ei:
        trainer.step(params, mom, aux, batch)
    assert "GC501" in str(ei.value)
    assert "RESOURCE_EXHAUSTED" in str(ei.value)

    monkeypatch.setenv("MXNET_TPU_DEVICE_HBM_GB", "16")
    trainer2, (p2, m2, a2) = _toy_trainer()
    p2, m2, a2, loss = trainer2.step(p2, m2, a2, batch)
    assert np.isfinite(float(loss))
    reports = [p for p in os.listdir(str(tmp_path))
               if p.startswith("preflight-trainer") and p.endswith(".json")]
    clean = Report.load(str(tmp_path / sorted(reports)[-1]))
    assert not [f for f in clean if f.rule == "GC501"]


# ---------------------------------------------------------------------------
# GC304: collectives serialized against compute (round 6)
# ---------------------------------------------------------------------------

# 2 MB sync all-reduce on the critical path: its only neighbors are its
# producer (multiply) and consumer (add) — nothing to hide behind
_GC304_SERIAL_HLO = """
ENTRY %main (p0: f32[524288]) -> f32[524288] {
  %p0 = f32[524288]{0} parameter(0)
  %w = f32[524288]{0} multiply(f32[524288]{0} %p0, f32[524288]{0} %p0)
  %ar = f32[524288]{0} all-reduce(f32[524288]{0} %w), replica_groups={}
  ROOT %out = f32[524288]{0} add(f32[524288]{0} %ar, f32[524288]{0} %ar)
}
"""

# same payload, but an independent dot exists in the computation — a
# double-buffered schedule any async backend can hide the transfer in
_GC304_PIPELINED_HLO = """
ENTRY %main (p0: f32[524288], q0: f32[128,128]) -> f32[524288] {
  %p0 = f32[524288]{0} parameter(0)
  %q0 = f32[128,128]{1,0} parameter(1)
  %ar = f32[524288]{0} all-reduce(f32[524288]{0} %p0), replica_groups={}
  %mm = f32[128,128]{1,0} dot(f32[128,128]{1,0} %q0, f32[128,128]{1,0} %q0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %out = f32[524288]{0} add(f32[524288]{0} %ar, f32[524288]{0} %ar)
}
"""


def test_gc304_seeded_all_sync_serial():
    rep = graphcheck.check_overlap(_GC304_SERIAL_HLO, target="toy")
    assert _rules(rep) == ["GC304"]
    (f,) = list(rep)
    assert f.severity == "warning"
    assert f.extra["sync_ops"] == 1 and f.extra["pipelined_ops"] == 0


def test_gc304_clean_when_overlap_exists():
    rep = graphcheck.check_overlap(_GC304_PIPELINED_HLO, target="toy")
    assert _rules(rep) == []


def test_gc304_tiny_payload_not_flagged():
    # the serial shape again, but 4 KB of payload: hiding a microsecond
    # transfer buys nothing — below MXNET_TPU_GC304_MIN_MB stays clean
    small = _GC304_SERIAL_HLO.replace("524288", "1024")
    assert _rules(graphcheck.check_overlap(small, target="toy")) == []
    # explicit floor override flags it again
    rep = graphcheck.check_overlap(small, target="toy", min_bytes=1)
    assert _rules(rep) == ["GC304"]


# ---------------------------------------------------------------------------
# GC305: pure-replica grad all-reduce while the ZeRO update is off
# ---------------------------------------------------------------------------

def test_gc305_seeded_replicated_update_at_payload():
    rep = graphcheck.check_zero_update(
        dp_size=8, update_sharded=False,
        grad_payload_bytes=45 << 20, target="toy")
    assert _rules(rep) == ["GC305"]
    (f,) = list(rep)
    assert f.severity == "warning"
    assert f.extra["dp_size"] == 8
    assert "MXNET_TPU_ZERO" in f.fix_hint


def test_gc305_clean_cases():
    # sharded update on -> clean at any payload
    rep = graphcheck.check_zero_update(8, True, 45 << 20, target="toy")
    assert _rules(rep) == []
    # dp=1: nothing is replicated, clean
    assert _rules(graphcheck.check_zero_update(1, False, 45 << 20)) == []
    # tiny payload under the default 8 MB floor: clean
    assert _rules(graphcheck.check_zero_update(8, False, 1 << 20)) == []
    # explicit floor override flags it again
    rep = graphcheck.check_zero_update(8, False, 1 << 20, min_bytes=1)
    assert _rules(rep) == ["GC305"]


def test_gc305_wired_into_check_trainer(monkeypatch):
    """check_trainer (the MXNET_TPU_PREFLIGHT=1 path) carries the rule:
    a dp trainer over a real payload warns unless the sharded update is
    on."""
    monkeypatch.setenv("MXNET_TPU_GC305_MIN_MB", "0.001")
    trainer, (params, mom, aux) = _toy_trainer()
    inputs = {"data": jax.ShapeDtypeStruct((8, 32), jnp.float32),
              "softmax_label": jax.ShapeDtypeStruct((8,), jnp.float32)}
    rep, _ = graphcheck.check_trainer(trainer, params, mom, aux, inputs)
    assert "GC305" in _rules(rep)
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    trainer2 = ShardedTrainer(trainer.symbol, trainer.spec, lr=0.1,
                              zero=True)
    p2, m2, a2 = trainer2.init_state(
        {"data": (8, 32), "softmax_label": (8,)})
    rep2, _ = graphcheck.check_trainer(trainer2, p2, m2, a2, inputs)
    assert "GC305" not in _rules(rep2)


def test_gc304_clean_on_ring_attention_program():
    """The double-buffered ring schedule (r6) must never flag: every
    ppermute has the block's attention dots to hide behind — even with
    the payload floor removed."""
    from mxnet_tpu.parallel.ring import local_ring_attention_fn
    n = 2
    mesh = _mesh(n, "sp")
    fn = local_ring_attention_fn("sp", False, 0.25, n)
    spec = P(None, "sp", None, None)
    mapped = _smap(fn, mesh, (spec,) * 3, spec)
    x = jnp.ones((1, 4 * n, 2, 8), jnp.float32)
    txt = jax.jit(mapped).lower(x, x, x).compile().as_text()
    rep = graphcheck.check_overlap(txt, target="ring", min_bytes=0)
    assert _rules(rep) == [], [f.message for f in rep]
