"""CPU tests of ``benchmark/lib/step_pipeline.py`` and of the six per-layer
readers on it: the table and each number on a small hand-made pipeline
(``data/step_pipeline.json``), the clock check on a copy with the device
plane shifted, each reader's ``None``, the script's summary, and a traced tiny
serve run whose trace holds what the engine writes for them."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import harness, program_trace, step_pipeline, trace  # noqa: E402

TINY = os.path.join(HERE, "tiny", "BENCHMARK.json")
NEW_METRICS = ["steps_starved_pct", "fetch_waited_pct",
               "completion_latency_ms", "token_gap_p99_ms",
               "request_ttft_p50_ms", "trace_clock_violation_us"]
NEW_ATTRS = ("prev_ready", "ready", "batch", "decoded", "stalled_ms")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "step_pipeline.json")) as f:
        doc = json.load(f)
    return ([tuple(s) for s in doc["spans"]], [tuple(o) for o in doc["ops"]],
            [tuple(e) for e in doc["events"]], doc["expect"])


def pipeline_of(spans, ops, events, shift_device_ns=0.0):
    """What ``step_pipeline.of_run`` makes of a run, from the plain lists;
    ``shift_device_ns`` moves every device event, as a device clock that
    runs behind the host's would."""
    events = [e if e[0] == trace.HOST_PLANE
              else e[:3] + (e[3] + shift_device_ns, e[4]) for e in events]
    run = program_trace.in_window(spans, ops)
    rows = step_pipeline.steps(run, trace.clip_to_window(events))
    return None if rows is None else (rows, run)


@pytest.fixture(scope="module")
def pipeline(recorded):
    return pipeline_of(*recorded[:3])


def reader(name):
    return harness.load_module(os.path.join(ROOT, "benchmark", "metrics",
                                            name + ".py")).read


# -- the table on the hand-made pipeline ------------------------------------------

def test_one_row_a_whole_run_beside_its_dispatch_and_its_fetch(
        recorded, pipeline):
    """Runs 10 and 20 are cut by the window's edges and dropped; the first
    whole run (11) lies beside the first dispatch that touches the window
    (step 11's, begun before it); step 20's and 21's dispatches are left
    without a run; the other device's runs and the program that ran once
    while the device idled are not the step's."""
    expect = recorded[3]
    rows = pipeline[0]
    assert [r.batch for r in rows] == expect["batches"]
    assert [r.gap / 1e3 for r in rows] == expect["gaps_us"]
    by_batch = {r.batch: r for r in rows}
    r14 = by_batch[14]
    assert r14.dispatch == (17100e3, 18100e3) and r14.run == (17100e3, 19100e3)
    assert r14.fetch == (19080e3, 19750e3)
    assert (r14.prev_ready, r14.ready) == (1, 0)
    # the late fetches: steps 13 and 17 had ended when the host came
    assert [b for b, r in by_batch.items() if r.ready] == [13, 17]
    assert [b for b, r in by_batch.items() if r.prev_ready] == [14, 18]


def test_each_reader_on_the_hand_made_pipeline(recorded, pipeline):
    """By hand.  Starved: runs 14 and 18 of nine began 600 and 25,350 us
    after the run before them.  Fetches begun in the window: steps 10-19's,
    of them 13's and 17's found their step ended.  Completion latency: steps
    13 and 17 were ready, the other seven read 600, 550, 650, 600, 26,600 (the
    stall), 650, 550, and the earliest run (14) starts as its dispatch does.  Token gaps between the ends of the ten retires,
    weighted 3 3 3 2 2 2 2 2 2: 2,050 2,000 3,050 1,550 2,000 27,950 1,350
    2,050 2,000 us; 99% of the weight of 21 lies under the stall's alone.
    Three requests ended ``ok`` with first tokens after 400, 600, 500 ms, one
    on a deadline."""
    expect = recorded[3]
    sp = step_pipeline
    assert sp.steps_starved_pct(pipeline) == pytest.approx(
        expect["steps_starved_pct"])
    assert sp.fetch_waited_pct(pipeline) == pytest.approx(
        expect["fetch_waited_pct"])
    assert [w / 1e3 for w in sp.completion_latencies_ns(pipeline[0])] \
        == expect["completion_latencies_us"]
    assert sp.completion_latency_ms(pipeline) == pytest.approx(
        expect["completion_latency_ms"])
    assert sp.token_gap_p99_ms(pipeline) == pytest.approx(
        expect["token_gap_p99_ms"])
    assert sp.request_ttft_p50_ms(pipeline) == pytest.approx(
        expect["request_ttft_p50_ms"])
    assert [m / 1e3 for m in sp.clock_minima(pipeline[0])] \
        == expect["clock_minima_us"]
    assert sp.trace_clock_violation_us(pipeline) == 0.0


def test_a_percentile_by_weight():
    pairs = [(2050, 3), (2000, 3), (3050, 3), (1550, 2), (2000, 2),
             (27950, 2), (1350, 2), (2050, 2), (2000, 2)]
    assert step_pipeline.weighted_percentile(pairs, 0.99) == 27950
    assert step_pipeline.weighted_percentile(pairs, 0.5) == 2000
    assert step_pipeline.weighted_percentile(pairs, 0.09) == 1350
    assert step_pipeline.weighted_percentile([(5, 0)], 0.5) is None
    assert step_pipeline.weighted_percentile([], 0.5) is None


def test_a_device_clock_300_us_behind_reads_as_300_and_not_0(recorded):
    """Step 14's run starts as its dispatch begins: with the device plane
    300 us earlier it starts 300 us BEFORE its dispatch began, which one
    clock does not allow.  The join does not slide to hide it: it goes by
    how evenly a fetch follows its run's end, which no shift changes."""
    spans, ops, events, expect = recorded
    shifted = pipeline_of(spans, ops, events, shift_device_ns=-300e3)
    assert [r.batch for r in shifted[0]] == expect["batches"]
    assert [m / 1e3 for m in step_pipeline.clock_minima(shifted[0])] \
        == [-300, 850]
    assert step_pipeline.trace_clock_violation_us(shifted) \
        == pytest.approx(300.0)
    # what compares durations, or instants of one clock, does not move
    for read in (step_pipeline.steps_starved_pct,
                 step_pipeline.fetch_waited_pct,
                 step_pipeline.token_gap_p99_ms,
                 step_pipeline.request_ttft_p50_ms):
        assert read(shifted) == read(pipeline_of(spans, ops, events))
    # nor the completion latency: fetch end - run end grows by 300 us and
    # the earliest run start - dispatch start falls by as much
    assert step_pipeline.completion_latency_ms(shifted) \
        == pytest.approx(expect["completion_latency_ms"])
    # and a device clock that runs 700 us AHEAD is caught from the other
    # side: step 12's fetch returns 150 us before the device ended it
    ahead = pipeline_of(spans, ops, events, shift_device_ns=700e3)
    assert [r.batch for r in ahead[0]] == expect["batches"]
    assert step_pipeline.trace_clock_violation_us(ahead) \
        == pytest.approx(150.0)


def test_the_join_goes_by_the_fetch_that_follows_its_run(recorded, pipeline):
    """With every run beside the dispatch after its own, a fetch would
    return 2.5-3.7 ms after "its" run ended, or 28 ms: the distances from
    their median have a median of 925 us, against 100 for the right table."""
    spans, ops, events, _expect = recorded
    run = program_trace.in_window(spans, ops)
    runs = step_pipeline.device_runs(trace.clip_to_window(events), run[2])
    calls = step_pipeline.dispatches(run[0])
    fetched = step_pipeline.fetches(run[0])
    assert len(calls) == 11 and sum(1 for r in runs if r[2]) == 9
    assert [r[2] for r in runs] == [False] + [True] * 9 + [False]
    right = step_pipeline._rows(calls, fetched, runs, 0)
    wrong = step_pipeline._rows(calls, fetched, runs, 1)
    assert right == pipeline[0] and [r.batch for r in wrong] == list(
        range(12, 21))
    assert step_pipeline.scatter_ns(right) == 100e3
    assert step_pipeline.scatter_ns(wrong) == 925e3


def test_the_summary_sorts_each_step_by_what_the_device_found(
        recorded, pipeline):
    """When the device ended the step before: seven of nine runs began at
    once (the step was queued), run 14 after 600 us, less than its dispatch
    lasted (1,000: under way), run 18 after 25,350 (not begun)."""
    expect = recorded[3]
    got = step_pipeline.summary(pipeline[0])
    assert [got["next_step_queued_pct"],
            got["ended_during_next_dispatch_pct"],
            got["ended_before_next_dispatch_pct"]] \
        == pytest.approx(expect["queued_raced_starved_pct"])
    assert got["steps"] == 9 and got["device_run_ms_median"] == 2.0
    assert got["starved_gap_ms_mean"] == pytest.approx((0.6 + 25.35) / 2)
    assert got["starved_gap_seconds"] == pytest.approx(25.95e-3)
    assert got["prev_ready_pct"] == pytest.approx(100 * 2 / 9)
    assert got["fetch_waited_pct"] == pytest.approx(100 * 7 / 9)
    assert got["completion_latency_ms_quartiles"][1] == pytest.approx(0.6)
    # steps 14 and 18 were launched on an idle device and then waited for:
    # dispatch start to fetch end, less the run of 2,000, is 650 and 1,050
    assert got["starved_round_trip_ms_quartiles"][1] == pytest.approx(0.85)
    assert got["run_start_minus_dispatch_start_us_min"] == 0.0
    assert got["fetch_end_minus_run_end_us_min"] == 550.0


def test_counts_that_the_edges_cannot_explain_give_no_table(recorded):
    spans, ops, events, _expect = recorded
    # every third run lost: nine dispatches too many for the window's edges
    modules = [e for e in events if e[1] == step_pipeline.MODULES_LINE]
    thinned = [e for e in events if e not in modules[::3]]
    assert pipeline_of(spans, ops, thinned) is None
    # a trace with no per-executable line, and one with no device at all
    assert pipeline_of(spans, ops, [e for e in events
                                    if e not in modules]) is None
    assert pipeline_of(spans, ops, [e for e in events
                                    if e[0] == trace.HOST_PLANE]) is None
    assert step_pipeline.steps(None, events) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_gives_none_and_does_not_raise(name, recorded, tmp_path,
                                              monkeypatch):
    read = reader(name)
    # a run that was not traced: no ``events`` among its facts
    assert read({"steps": 3}) is None
    assert read({"events": [], "traced": {"window_s": 1.0}}) is None
    # traced, but nothing on disk
    monkeypatch.setattr(program_trace, "TRACE_DIR", str(tmp_path / "none"))
    assert read({"events": [("/host:CPU", "python", "x", 0.0, 1.0)]}) is None
    # a trace of a commit before PR 37: the same spans without the new attrs
    # and without serve/request_done
    spans, ops, events, _expect = recorded
    old = [s[:4] + ({k: v for k, v in s[4].items() if k not in NEW_ATTRS},)
           for s in spans if s[1] != "serve/request_done"]
    facts = {"events": trace.clip_to_window(events)}
    monkeypatch.setattr(program_trace, "of_run",
                        lambda _facts: program_trace.in_window(old, ops))
    assert read(facts) is None
    # and with them, the number
    monkeypatch.setattr(program_trace, "of_run",
                        lambda _facts: program_trace.in_window(spans, ops))
    assert read(facts) is not None


def test_the_script_prints_the_summary(recorded, tmp_path, monkeypatch,
                                       capsys):
    spans, ops, events, _expect = recorded
    fake = tmp_path / "x.xplane.pb"
    fake.write_bytes(b"")
    monkeypatch.setattr(program_trace, "load",
                        lambda path: program_trace.in_window(spans, ops))
    monkeypatch.setattr(trace, "load_events",
                        lambda path, host_names=(): events)
    assert step_pipeline.main(["step_pipeline.py", str(fake)]) == 0
    out = capsys.readouterr().out
    assert "ended_during_next_dispatch_pct" in out and "11.1111" in out
    assert "completion_latency_ms_quartiles" in out
    assert "fetch_end_minus_run_end_us_min" in out and "550.0000" in out
    assert step_pipeline.main(["step_pipeline.py", "a", "b"]) == 2


# -- a traced tiny run: what the engine writes for them is in the trace -----------

@pytest.fixture(scope="module")
def tiny_with_new_metrics(tmp_path_factory):
    """The tiny benchmark beside this file with the six new per-layer
    entries of the real BENCHMARK.json moved onto its serve cell."""
    tiny = harness.load_json(TINY)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    tiny_root = os.path.dirname(TINY)
    tiny["paths"] = [os.path.join(tiny_root, p) for p in tiny["paths"]]
    for c in tiny["configs"]:
        c["file"] = os.path.join(tiny_root, c["file"])
    new = [m for m in real["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == NEW_METRICS
    tiny["per_layer"] += [dict(m, workloads=["tiny.serve"]) for m in new]
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def test_traced_tiny_serve_run_holds_what_the_engine_knew(
        tiny_with_new_metrics, tmp_path, monkeypatch):
    import io
    # a trace directory of this test's own: the checkout's is emptied by
    # every traced run, and other workers make such runs meanwhile
    monkeypatch.setattr(harness, "trace_dir", lambda root: str(tmp_path))
    monkeypatch.setattr(program_trace, "TRACE_DIR", str(tmp_path))
    import mxnet_tpu  # noqa: F401
    import jax
    from benchmark import run as bench_run
    err = io.StringIO()
    drivers = []

    def kept(Driver):
        class Kept(Driver):
            def __init__(self, ctx):
                super().__init__(ctx)
                drivers.append(self)
        return Kept

    result = bench_run.execute(
        tiny_with_new_metrics, "tiny.serve", 2**31 + 37, 0.4, True,
        jax.devices()[:1], driver_class=kept, err=err,
        peaks_for_tests={"flops": 1e12, "hbm_bytes_per_s": 1e11,
                         "hbm_bytes": 1e10})
    assert result["correct"] is True, err.getvalue()
    # no device plane on the CPU: no reader has a device run to put beside
    # a dispatch, and none reports
    for name in NEW_METRICS:
        assert name not in result["metrics"]
    kept, _cut, (w0, w1) = program_trace.load(
        trace.find_xplane(program_trace.TRACE_DIR))
    inside = [s for s in kept if w0 <= s[2] and s[2] + s[3] <= w1]
    steps = {s[4]["batch"]: s for s in inside
             if s[1] == "serve/decode_step"}
    fetched = [s for s in inside if s[1] == "serve/fetch" and s[4]]
    assert steps and fetched
    for s in fetched:
        assert set(s[4]) == {"batch", "ready"} and s[4]["ready"] in (0, 1)
    # a fetch names a step dispatched before the one whose span it lies in
    # (a drain iteration's lies in none)
    around = [(s, t) for s in fetched for t in steps.values()
              if t[2] <= s[2] and s[2] + s[3] <= t[2] + t[3]]
    assert around
    for s, step in around:
        assert s[4]["batch"] < step[4]["batch"]
        assert step[4]["in_flight"] == 1 and step[4]["prev_ready"] in (0, 1)
    retires = [s for s in inside if s[1] == "serve/retire"]
    assert retires and all("decoded" in s[4] and "retired" in s[4]
                           for s in retires)
    assert sum(s[4]["decoded"] for s in retires) > 0
    # one serve/request_done a finished request, inside the retire that
    # settled it, and what it says is what a caller got back: a prompt of
    # that length answered with that many tokens
    done = [s for s in inside if s[1] == "serve/request_done"]
    assert len(done) == sum(s[4]["retired"] for s in retires) > 0
    came_back = {(len(prompt), len(ids))
                 for _t0, _t1, prompt, ids in drivers[0].done}
    for s in done:
        a = s[4]
        assert s[3] < 1e5                   # no length (under 0.1 ms)
        assert a["outcome"] == "ok"
        assert (a["n_prompt"], a["n_generated"]) in came_back
        assert 0 <= a["queue_wait_us"] <= a["ttft_us"] <= a["total_us"]
        assert a["n_generated"] == 1 or a["itl_max_us"] > 0
        assert any(r[2] <= s[2] <= r[2] + r[3] for r in retires)
