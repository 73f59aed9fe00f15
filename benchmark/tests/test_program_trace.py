"""CPU tests of ``benchmark/lib/program_trace.py`` and of the per-layer readers
that sit on it: the arithmetic on a small recorded list
(``data/program_trace.json``), each reader's ``None``, the file reader against
``jax.profiler.ProfileData`` on a trace taken here, and a traced tiny serve run
whose trace holds the engine's own spans."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import harness, program_trace, trace  # noqa: E402

TINY = os.path.join(HERE, "tiny", "BENCHMARK.json")
NEW_METRICS = ["engine_host_ms", "device_idle_named_pct.serve",
               "guard_device_pct", "batchnorm_device_pct",
               "kv_write_device_pct", "device_scoped_pct.train",
               "device_scoped_pct.serve"]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "program_trace.json")) as f:
        doc = json.load(f)
    spans = [tuple(s) for s in doc["spans"]]
    ops = [tuple(o) for o in doc["ops"]]
    return spans, ops, doc["expect"]


@pytest.fixture(scope="module")
def run(recorded):
    spans, ops, _expect = recorded
    return program_trace.in_window(spans, ops)


def reader(name):
    return harness.load_module(os.path.join(ROOT, "benchmark", "metrics",
                                            name + ".py")).read


# -- the arithmetic on the recorded list ----------------------------------------

def test_window_is_the_longest_bench_window_and_cuts_the_ops(recorded, run):
    spans, ops, expect = recorded
    kept, cut, window = run
    assert list(window) == expect["window"]
    # the span and the operation wholly before the window are gone, the
    # ``while`` wrapper too; an operation across an edge counts by its part
    assert len(kept) == len(spans) - 4 and len(cut) == len(ops) - 2
    assert ("mxt-serving#77", "serve/fetch", 11200, 2700, {}) not in kept
    by_op = {o[1]: o for o in cut if o[0] == "/device:TPU:0"}
    assert "while.5" not in by_op and "fusion.0" not in by_op
    assert by_op["fusion.9"][3:] == (10500, 500)
    assert program_trace.in_window(
        [s for s in spans if s[1] != trace.WINDOW_SPAN], ops) is None


@pytest.mark.parametrize("path,scope", [
    ("jit(step_fn)/transpose(jvp(mx.BatchNorm.stage1_unit1_bn1))/mul:",
     "mx.BatchNorm.stage1_unit1_bn1"),
    ("jit(step_fn)/jvp(mx.FullyConnected.l3_ff1)/dot_general:",
     "mx.FullyConnected.l3_ff1"),
    ("jit(step_fn)/mx.guard/reduce_and:", "mx.guard"),
    ("jit(step)/mx.decode.kv_write/scatter:", "mx.decode.kv_write"),
    ("jit(f)/mx.update/mx.guard/select_n:", "mx.guard"),
    ("jit(step_fn)/jvp()/reduce_sum:", None),
    ("", None),
])
def test_scope_of_an_op_name_path(path, scope):
    assert program_trace.scope_of(path) == scope


def test_device_seconds_by_scope(recorded, run):
    expect = recorded[2]
    got = program_trace.seconds_by_scope(run[1])
    assert got == pytest.approx(expect["seconds_by_scope"], rel=1e-9)


def test_idle_seconds_go_to_the_innermost_span_of_any_thread(recorded, run):
    """By hand, on the first device, each gap shared out by overlap: the
    window's first 50 ns fall in serve/admit; 1500-1900 is 300 of the first
    serve/dispatch and 100 of serve/fetch (both inside serve/decode_step);
    2100-2300 in compile/decode_step, which another thread opened after
    serve/fetch; 2900-3100 in serve/fetch; 5280-5380 is 20 of serve/retire,
    50 under nothing and 30 of the next serve/admit; 5400-6500 runs through
    admit 100, build 300, decode_step's own 100, dispatch 300, fetch 300;
    8500-10500 through fetch 1200, decode_step 100, retire 400, admit 200,
    build 100."""
    expect = recorded[2]
    got = program_trace.idle_seconds_by_span(run)
    assert got == pytest.approx(expect["idle_seconds_by_span"], rel=1e-9)
    window = (expect["window"][1] - expect["window"][0]) / 1e9
    busy = trace.union_seconds([(o[3], o[3] + o[4]) for o in run[1]
                                if o[0] == "/device:TPU:0"]) / 1e9
    assert sum(got.values()) == pytest.approx(window - busy, rel=1e-9)


def test_each_reader_on_the_recorded_list(recorded, run):
    expect = recorded[2]
    pt = program_trace
    assert pt.engine_host_ms(run) == pytest.approx(expect["engine_host_ms"])
    assert pt.idle_named_pct(run) == pytest.approx(expect["idle_named_pct"])
    assert pt.scoped_pct(run) == pytest.approx(expect["scoped_pct"])
    for key, wanted in (
            ("guard_pct", lambda s: s == "mx.guard"),
            ("batchnorm_pct", lambda s: s.startswith("mx.BatchNorm.")),
            ("kv_write_pct", lambda s: s == "mx.decode.kv_write")):
        assert pt.scope_share_pct(run, wanted) == pytest.approx(expect[key])
    table = pt.span_table(run)
    assert table[0] == ("serve/decode_step", 3, pytest.approx(11000e-9))
    assert table[1] == ("serve/fetch", 2, pytest.approx(6600e-9))


def test_a_trace_without_spans_or_scopes_reads_as_nothing(recorded):
    """The parent of PR 26: operations with paths but no ``mx.`` scope, no
    program span but the harness's window."""
    spans, ops, _expect = recorded
    pt = program_trace
    bare = pt.in_window(
        [s for s in spans if s[1] == trace.WINDOW_SPAN],
        [o[:2] + (o[2].replace("mx.", "") if o[2] else "",) + o[3:]
         for o in ops])
    assert pt.engine_host_ms(bare) is None
    assert pt.idle_named_pct(bare) is None
    assert pt.scoped_pct(bare) is None      # flash_fwd alone names nothing
    assert pt.scope_share_pct(bare, lambda s: s == "mx.guard") is None
    # scopes, but none of the wanted kind: the metric is gone, not 0
    some = pt.in_window(spans, [o for o in ops if "BatchNorm" not in o[2]])
    assert pt.scope_share_pct(
        some, lambda s: s.startswith("mx.BatchNorm.")) is None
    assert pt.scope_share_pct(some, lambda s: s == "mx.guard") > 0
    for fn in (pt.engine_host_ms, pt.idle_named_pct, pt.scoped_pct):
        assert fn(None) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_gives_none_and_does_not_raise(name, tmp_path, monkeypatch):
    read = reader(name)
    # a run that was not traced: no ``events`` among its facts
    assert read({"steps": 3}) is None
    assert read({"events": [], "traced": {"window_s": 1.0}}) is None
    # traced, but nothing on disk
    monkeypatch.setattr(program_trace, "TRACE_DIR", str(tmp_path / "none"))
    assert read({"events": [("/host:CPU", "python", "x", 0.0, 1.0)]}) is None


# -- the file reader --------------------------------------------------------------

def test_read_xplane_agrees_with_profile_data(tmp_path):
    """On a trace taken here: the same spans at the same times as
    ``jax.profiler.ProfileData`` gives (``lib/trace.load_events``), with the
    attrs and the thread that it leaves out."""
    import threading
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import telemetry

    def other():
        with telemetry.span("serve/build", cat="serve", slots=3):
            pass

    with harness.profiler_window(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with telemetry.span("train/step", cat="train", step=4,
                                what="x") as sp:
                jnp.ones((8, 8)).sum().block_until_ready()
                sp.annotate(skipped=0)
            t = threading.Thread(target=other, name="engine")
            t.start()
            t.join()
        with jax.profiler.TraceAnnotation("not/ours"):
            pass
    path = trace.find_xplane(str(tmp_path))
    spans, ops = program_trace.read_xplane(path)
    assert program_trace.load(path) is program_trace.load(path)
    assert ops == []                        # no device plane on the CPU
    names = (trace.WINDOW_SPAN, "train/step", "serve/build")
    seen = sorted((e[2], e[3], e[4]) for e in
                  trace.load_events(path, host_names=names + ("not/ours",))
                  if e[2] in names)
    assert sorted((s[1], s[2], s[3]) for s in spans) == seen
    by_name = {s[1]: s for s in spans}
    assert set(by_name) == set(names)
    assert by_name["train/step"][4] == {"step": 4, "what": "x",
                                        "skipped": 0}
    assert by_name["serve/build"][4] == {"slots": 3}
    assert by_name["serve/build"][0] != by_name["train/step"][0]
    assert by_name["train/step"][0] == by_name[trace.WINDOW_SPAN][0]
    kept, _cut, (w0, w1) = program_trace.load(path)
    assert {s[1] for s in kept} == {"train/step", "serve/build"}
    assert w1 > w0


# -- a traced tiny run: the engine's own spans are in the benchmark's trace -------

@pytest.fixture(scope="module")
def tiny_with_new_metrics(tmp_path_factory):
    """The tiny benchmark beside this file with the new per-layer entries of
    the real BENCHMARK.json moved onto its cells."""
    tiny = harness.load_json(TINY)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    tiny_root = os.path.dirname(TINY)
    tiny["paths"] = [os.path.join(tiny_root, p) for p in tiny["paths"]]
    for c in tiny["configs"]:
        c["file"] = os.path.join(tiny_root, c["file"])
    cells = {"serve_tokens_s": ["tiny.serve"],
             "train_step_ms": ["tiny.train", "tiny.resnet-f32"]}
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            tiny["per_layer"].append(dict(m, workloads=cells[m["moves"]]))
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def test_traced_tiny_serve_run_reads_the_engines_own_spans(
        tiny_with_new_metrics):
    import io
    import mxnet_tpu  # noqa: F401
    import jax
    from benchmark import run as bench_run
    err = io.StringIO()
    result = bench_run.execute(
        tiny_with_new_metrics, "tiny.serve", 2**31 + 26, 0.4, True,
        jax.devices()[:1], err=err,
        peaks_for_tests={"flops": 1e12, "hbm_bytes_per_s": 1e11,
                         "hbm_bytes": 1e10})
    assert result["correct"] is True, err.getvalue()
    metrics = result["metrics"]
    assert metrics["engine_host_ms"]["value"] > 0
    # no device plane on the CPU: nothing idles, nothing carries a scope
    for name in NEW_METRICS[1:]:
        assert name not in metrics
    path = trace.find_xplane(program_trace.TRACE_DIR)
    spans, _ops = program_trace.read_xplane(path)
    kept, _cut, (w0, w1) = program_trace.load(path)
    engine = {s[0] for s in kept if s[1] == "serve/decode_step"}
    assert len(engine) == 1     # one thread, and not the harness's
    assert engine.isdisjoint({s[0] for s in spans
                              if s[1] == trace.WINDOW_SPAN})
    names = {s[1] for s in kept if w0 <= s[2] and s[2] + s[3] <= w1}
    assert {"serve/admit", "serve/build", "serve/decode_step",
            "serve/dispatch", "serve/fetch", "serve/retire"} <= names
    step = next(s for s in kept if s[1] == "serve/decode_step")
    assert {"batch", "slots", "n_prefill", "n_decode",
            "attended"} <= set(step[4])
    inner = [s for s in kept if s[1] in ("serve/dispatch", "serve/fetch")
             and step[2] <= s[2] and s[2] + s[3] <= step[2] + step[3]]
    assert {s[1] for s in inner} == {"serve/dispatch", "serve/fetch"}


def test_the_tool_prints_both_tables(recorded, tmp_path, monkeypatch, capsys):
    spans, ops, _expect = recorded
    fake = tmp_path / "x.xplane.pb"
    fake.write_bytes(b"")
    monkeypatch.setattr(program_trace, "load",
                        lambda path: program_trace.in_window(spans, ops))
    assert program_trace.main(["program_trace.py", str(fake)]) == 0
    out = capsys.readouterr().out
    assert "device seconds by scope" in out and "mx.BatchNorm.*" in out
    assert "outside every scope" in out and "copy  -" in out
    assert "idle seconds of the first device" in out
    assert "compile/decode_step" in out and "(none)" in out
    assert program_trace.main(["program_trace.py"]) == 2
