"""One clock (ISSUE 26): every ``telemetry.span`` is a
``jax.profiler.TraceAnnotation``, the decode engine's host loop names its
parts, the jitted steps carry ``mx.*`` scopes that do not move the compile
cache's keys, and requests carry per-token stamps."""
import contextlib
import glob
import os
import re
import threading

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.compile import program_fingerprint
from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
from mxnet_tpu.parallel.trainer import ShardedTrainer
from mxnet_tpu.serving.decode import (DecodeConfig, DecodeEngine,
                                      DecodeProgram, init_decode_params)
from mxnet_tpu.telemetry import spans as spans_mod

@pytest.fixture(autouse=True)
def _clean():
    yield
    telemetry.reset()


@pytest.fixture
def recorder(monkeypatch):
    """Stands in for ``jax.profiler.TraceAnnotation``: a log of
    ``(thread, what, name, attrs)`` in the order things happened."""
    log = []

    class Recorder:
        def __init__(self, name, **attrs):
            self.name = name
            self.attrs = dict(attrs)

        def _note(self, what, attrs=None):
            log.append((threading.current_thread().name, what, self.name,
                        dict(self.attrs if attrs is None else attrs)))

        def __enter__(self):
            self._note("enter")
            return self

        def set_metadata(self, **attrs):
            self._note("meta", attrs)

        def __exit__(self, *exc):
            self._note("exit")
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return log


# -- part 1: a span is a TraceAnnotation ----------------------------------

@pytest.mark.parametrize("armed", [False, True], ids=["off", "armed"])
def test_span_enters_and_exits_one_annotation(recorder, armed):
    if armed:
        telemetry.arm()
    assert spans_mod.spans_active() is armed
    with telemetry.span("train/step", cat="train", step=7, lr=0.5,
                        who="me", shape=(2, 3), none=None) as outer:
        with telemetry.span("train/host_enqueue"):
            pass
        outer.annotate(skipped=1, why=[1])
    me = threading.current_thread().name
    plain = {"step": 7, "lr": 0.5, "who": "me", "shape": "(2, 3)",
             "none": "None"}
    assert recorder == [
        (me, "enter", "train/step", plain),
        (me, "enter", "train/host_enqueue", {}),
        (me, "exit", "train/host_enqueue", {}),
        (me, "meta", "train/step", {"skipped": 1, "why": "[1]"}),
        (me, "exit", "train/step", plain),
    ]
    # the span's own consumers see the late attrs beside the rest
    assert outer.attrs["skipped"] == 1 and outer.attrs["step"] == 7
    assert outer.active is armed


def test_span_annotation_exits_on_error(recorder):
    with pytest.raises(ValueError):
        with telemetry.span("serve/build", slots=1):
            raise ValueError("x")
    assert [r[1] for r in recorder] == ["enter", "exit"]


def test_profiler_trace_holds_spans_and_arms_nothing(tmp_path):
    """A real ``jax.profiler`` trace holds the spans with their attrs on
    the host plane, and does not make ``spans_active()`` true: a span that
    changes what it measures when armed (``train/device_wait`` blocks on
    the device) must not start to because somebody is looking."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert not spans_mod.spans_active()
        with telemetry.span("serve/build", cat="serve", slots=3) as sp:
            with telemetry.span("serve/dispatch", cat="serve"):
                pass
            sp.annotate(retired=2)
        assert not sp.active
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve/"):
                    found[ev.name] = (ev.start_ns, ev.end_ns,
                                      dict(ev.stats))
    assert found["serve/build"][2] == {"slots": 3, "retired": 2}
    b, d = found["serve/build"], found["serve/dispatch"]
    assert b[0] <= d[0] and d[1] <= b[1]


# -- part 2: the engine's host loop ----------------------------------------

VOCAB, T, L, H, HEADS = 29, 16, 2, 24, 2


@pytest.fixture(scope="module")
def toy():
    cfg = DecodeConfig(VOCAB, L, H, HEADS, T, page_size=4, max_seqs=3)
    prog = DecodeProgram(init_decode_params(cfg, seed=3), cfg, name="toy26")
    prog.ensure_compiled()
    return cfg, prog


def _engine_log(recorder):
    return [r for r in recorder if r[0] == "mxt-serving"
            and r[2].startswith("serve/")]


def test_engine_emits_its_six_spans_once_a_step(toy, recorder):
    cfg, prog = toy
    requests = [(np.arange(5) % VOCAB, 4), (np.arange(2) % VOCAB, 6),
                (np.arange(7) % VOCAB, 3), (np.arange(3) % VOCAB, 2)]
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        futures = [eng.submit(p, max_new_tokens=m) for p, m in requests]
        outs = [f.result(timeout=60.0)[0] for f in futures]
        stats = eng.stats()
    assert [len(o) for o in outs] == [m for _p, m in requests]

    # a request of n prompt and m new tokens is fed n + m - 1 tokens, at
    # contexts 1, 2, ..., n + m - 1
    fed = [len(p) + m - 1 for p, m in requests]
    attended = sum(f * (f + 1) // 2 for f in fed)
    assert stats["decode"]["contexts_attended"] == attended
    assert stats["counters"]["contexts_attended"] == attended
    # and the pages those contexts lie on: ceil(context / page) each step
    pages = sum(-(-ctx // cfg.page_size) for f in fed
                for ctx in range(1, f + 1))
    assert stats["decode"]["pages_attended"] == pages
    assert stats["counters"]["pages_attended"] == pages
    steps = stats["counters"]["steps"]
    assert steps >= max(fed)

    log = _engine_log(recorder)
    # an iteration that finds no work emits serve/admit alone: drop those
    order = [(what, name) for _t, what, name, _a in log if what != "meta"]
    # a settled request's serve/request_done (PR 37) lies inside the
    # serve/retire that settled it and is no span of the loop's own
    done_at = [i for i, item in enumerate(order)
               if item == ("enter", "serve/request_done")]
    assert len(done_at) == len(requests)
    for i in done_at:
        assert order[i + 1] == ("exit", "serve/request_done")
        inside = [name for what, name in order[:i] if what == "enter"
                  and name != "serve/request_done"][-1]
        assert inside == "serve/retire"
    order = [item for item in order if item[1] != "serve/request_done"]
    kept = []
    for i, item in enumerate(order):
        lone = (item == ("exit", "serve/admit")
                and order[i + 1:i + 2] not in ([("enter", "serve/build")],
                                               [("enter", "serve/fetch")]))
        if lone:
            assert kept.pop() == ("enter", "serve/admit")
            continue
        kept.append(item)
    # The loop keeps one step in flight, so the fetch and the retire of
    # iteration k are step k-1's.  An iteration that dispatches emits all
    # six spans in the old order; the one that starts a pipeline has
    # nothing in flight (in_flight=0) and its fetch and retire are empty.
    # When nothing is left to dispatch the step in flight is taken in by a
    # drain iteration: serve/admit, then serve/fetch and serve/retire with
    # no serve/decode_step round them.
    admit = [("enter", "serve/admit"), ("exit", "serve/admit")]
    taken_in = [("enter", "serve/retire"), ("exit", "serve/retire")]
    fetch = [("enter", "serve/fetch"), ("exit", "serve/fetch")]
    one_step = admit + [("enter", "serve/build"), ("exit", "serve/build"),
                        ("enter", "serve/decode_step"),
                        ("enter", "serve/dispatch"),
                        ("exit", "serve/dispatch")] + fetch \
        + [("exit", "serve/decode_step")] + taken_in
    drain = admit + fetch + taken_in
    shape, at = [], 0           # "step" or "drain", iteration by iteration
    while at < len(kept):
        for kind, spans in (("step", one_step), ("drain", drain)):
            if kept[at:at + len(spans)] == spans:
                shape.append(kind)
                at += len(spans)
                break
        else:
            raise AssertionError("iteration %d: %s" % (len(shape),
                                                       kept[at:at + 12]))
    assert shape.count("step") == steps
    assert shape[-1] == "drain"         # the last step's tokens came in
    # a drain follows a step (there is one step in flight, never two)
    assert all(shape[i - 1] == "step" for i, kind in enumerate(shape)
               if kind == "drain") and shape[0] == "step"

    entered = [(name, attrs) for _t, what, name, attrs in log
               if what == "enter"]
    step_attrs = [a for n, a in entered if n == "serve/decode_step"]
    assert sum(a["attended"] for a in step_attrs) == attended
    assert all(a["n_prefill"] + a["n_decode"] == a["slots"]
               for a in step_attrs)
    assert sum(a["n_prefill"] for a in step_attrs) \
        == stats["decode"]["tokens_prefilled"]
    assert sum(a["n_decode"] for a in step_attrs) \
        == stats["decode"]["tokens_decoded"]
    assert [a["batch"] for a in step_attrs] == list(range(1, steps + 1))
    # in_flight is 0 on the step that starts a pipeline (the first, and
    # the one after each drain) and 1 on every other
    starts = [i == 0 or shape[i - 1] == "drain"
              for i, kind in enumerate(shape) if kind == "step"]
    assert [a["in_flight"] for a in step_attrs] == [int(not st)
                                                    for st in starts]
    assert stats["decode"]["steps_overlapped"] == steps - sum(starts)
    assert all(set(a) == {"slots"} for n, a in entered
               if n == "serve/build")
    metas = {}
    for _t, what, name, attrs in log:
        if what == "meta":
            for k, v in attrs.items():
                metas[name, k] = metas.get((name, k), 0) + v
    assert metas["serve/admit", "admitted"] == len(requests)
    assert metas["serve/retire", "retired"] == len(requests)
    assert all("queued" in a for n, a in entered if n == "serve/admit")


def test_dispatch_span_counts_what_a_call_hands_over(toy, recorder):
    """``serve/dispatch`` says what its call handed the runtime: ONE host
    array (the packed operands; the last step's tokens stay on the device,
    and the first step's stand-in lies there too) and the device arrays
    (every parameter, the pool, those tokens).  ``stats()`` has the host
    arrays a step, exact over a run."""
    cfg, prog = toy
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        futures = [eng.submit(np.arange(n) % VOCAB, max_new_tokens=m)
                   for n, m in ((4, 5), (2, 3), (6, 1))]
        for f in futures:
            f.result(timeout=60.0)
        stats = eng.stats()
    steps = stats["counters"]["steps"]
    dispatches = [attrs for _t, what, name, attrs in _engine_log(recorder)
                  if what == "enter" and name == "serve/dispatch"]
    assert len(dispatches) == steps > 0
    assert all(a == {"host_operands": 1,
                     "device_args": len(prog._params) + 2}
               for a in dispatches)
    assert stats["counters"]["host_operands"] == steps
    assert stats["decode"]["host_operands_per_step"] == 1.0
    assert prog._operands.size == 5 * cfg.max_seqs \
        + cfg.max_seqs * cfg.pages_per_seq


def test_request_stamps_and_their_percentiles(toy):
    cfg, prog = toy
    with DecodeEngine(prog, default_deadline=60.0) as eng:
        reqs = [eng.submit(np.arange(n) % VOCAB, max_new_tokens=m)
                for n, m in ((4, 5), (2, 3), (6, 1))]
        for r in reqs:
            r.result(timeout=60.0)
        decode = eng.stats()["decode"]
    for r in reqs:
        assert len(r.token_times) == len(r.generated) == r.max_new
        assert list(r.token_times) == sorted(r.token_times)
        assert r.enqueued_at <= r.t_dispatched <= r.token_times[0]
        assert r.token_times[-1] <= r.done_at
    for key in ("token_step_s", "queue_wait_s", "ttft_s", "itl_s"):
        assert 0.0 <= decode[key]["p50"] <= decode[key]["p99"], key
    # first tokens: one per request; gaps: the rest
    assert eng._ttft_hist.summary()["count"] == 3
    assert eng._itl_hist.summary()["count"] == (5 - 1) + (3 - 1)
    assert eng._qwait_hist.summary()["count"] == 3


# -- part 3: stable names on the device ------------------------------------

def _net():
    d = mx.sym.Variable("data")
    f1 = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    b = mx.sym.BatchNorm(f1, name="bn1")
    a = mx.sym.Activation(b, act_type="relu", name="relu1")
    f2 = mx.sym.FullyConnected(a, num_hidden=2, name="fc2")
    return mx.sym.SoftmaxOutput(f2, name="softmax")


def _lower_trainer_step(zero=False, n_dev=1):
    spec = MeshSpec(make_mesh((n_dev,), ("dp",),
                              devices=jax.devices()[:n_dev]))
    tr = ShardedTrainer(_net(), spec, lr=0.01, momentum=0.9, wd=0.0,
                        zero=zero)
    p, m, a = tr.init_state({"data": (12, 4), "softmax_label": (12,)},
                            seed=3)
    inputs = {"data": jax.ShapeDtypeStruct((12, 4), np.float32),
              "softmax_label": jax.ShapeDtypeStruct((12,), np.float32)}
    with tr._tracing_on_mesh():
        return tr._build_step(donate=False).lower(
            p, m, a, inputs, tr._keys(), tr._guard_arrays())


def _lower_decode_step():
    cfg = DecodeConfig(VOCAB, L, H, HEADS, T, page_size=4, max_seqs=3)
    prog = DecodeProgram(init_decode_params(cfg, seed=3), cfg, name="low26")
    return jax.jit(prog._make_step_fn(count=False)).lower(
        prog._params, prog.fresh_cache(), *prog._zero_step_args())


TRAINER_SCOPES = {"mx.update", "mx.guard", "mx.loss_scale",
                  "mx.FullyConnected.fc1", "mx.BatchNorm.bn1",
                  "mx.Activation.relu1", "mx.FullyConnected.fc2",
                  "mx.SoftmaxOutput.softmax"}
DECODE_SCOPES = {"mx.decode." + s for s in
                 ("embed", "ln", "qkv", "kv_write", "attn", "proj", "mlp",
                  "head", "sample")}


@pytest.mark.parametrize("lower, scopes", [
    (_lower_trainer_step, TRAINER_SCOPES),
    (_lower_decode_step, DECODE_SCOPES),
], ids=["trainer", "decode"])
def test_lowered_step_holds_scopes_and_keeps_its_fingerprint(
        monkeypatch, lower, scopes):
    lowered = lower()
    named = set(re.findall(r"mx\.[A-Za-z0-9_.]+",
                           lowered.as_text(debug_info=True)))
    assert named == scopes
    # what compile/cache.program_fingerprint hashes carries no debug info
    text = lowered.as_text()
    assert "mx." not in text
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower()
    assert "mx." not in bare.as_text(debug_info=True)
    assert program_fingerprint(bare.as_text()) == program_fingerprint(text)


def test_backward_ops_inherit_the_node_scope():
    dbg = _lower_trainer_step().as_text(debug_info=True)
    assert re.search(r"transpose\(jvp\(mx\.BatchNorm\.bn1\)\)", dbg)
    assert re.search(r"jvp\(mx\.FullyConnected\.fc1\)", dbg)


def test_zero_step_names_its_scatter_and_gather():
    dbg = _lower_trainer_step(zero=True, n_dev=2).as_text(debug_info=True)
    assert {"mx.zero_scatter", "mx.zero_gather"} <= set(
        re.findall(r"mx\.[A-Za-z0-9_.]+", dbg))
