#!/usr/bin/env python3
"""The decode pipeline of a traced serve run, one row a step: when the host
enqueued the step (``serve/dispatch``), when the device ran it, when the host
learned that it had ended (``serve/fetch``), and what the host knew at both
moments (``prev_ready`` on ``serve/decode_step``, ``ready`` on the fetch).

The host's side comes from ``program_trace.of_run`` (the engine's spans with
their attrs), the device's from ``facts["events"]``: libtpu's ``XLA Modules``
line holds one event a run of a compiled program (probed on the v5e: PR 25's
recorded trace and PR 37's serve traces both have it), and the step program
is the one that runs most often in the window.  Runs that the window's edge
cut (``trace.clip_to_window``) are dropped.

**The join.**  Runs execute in dispatch order, one a ``serve/dispatch``, and
a fetch names its step (``batch``); so the table is fixed up to one whole
number, by how many places the window's first run lies from its first
dispatch.  Causality alone does not fix it: on the v5e the trace's device
clock reads 0.5-1.7 ms early against the host's (PERF.md section 3), half of
a 2 ms step, and then the right number and its neighbour contradict one clock
by much the same (0.58 against 0.81 ms in one trace).  What fixes it is that
a step's fetch returns a near-constant time after its run ended (the
runtime's notice follows the device's completion), whatever the clocks'
offset, while a wrong number adds a loop period's jitter to every row (the
median distance from the median 0.08 against 0.19 ms there).  ``steps`` takes
the number under which ``fetch end - run end`` scatters least and reports
what causality then still contradicts: the smallest ``run start - dispatch
start`` and ``fetch end - run end`` over the window (``clock_minima``) bound
the clocks' offset from both sides, and a negative one is a measured
violation.  Every other number here compares only durations across the two
clocks, or instants of one; ``completion_latency_ms`` adds one difference in
each direction, so that the offset cancels.

Every reader gives None, and does not raise, where the run was not traced,
the trace holds no device plane (the CPU) or no window, or the program wrote
no ``batch`` on its fetches (every commit before PR 37).

Run as ``python3 benchmark/lib/step_pipeline.py [trace dir or .xplane.pb]``
it prints the table's summary for the trace ``run.py --trace 1`` left.
"""
import collections
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import program_trace, trace  # noqa: E402

# the line of a device plane that carries one event a run of an executable
MODULES_LINE = "XLA Modules"
# a run that began later than this after the one before it ended found the
# device with nothing queued
STARVED_NS = 50e3
# places the window's first whole run may lie from its first dispatch: its
# own dispatch ended before the window (-1), is the first (0), or follows
# the dispatch of the run the window's start cut (1)
OFFSETS = (-1, 0, 1)
# dispatches the window's end may leave without a whole run: one whose run
# the edge cut, one whose run had not begun
TAIL = 2

# one step: ``dispatch``, ``run`` and ``fetch`` are (start_ns, end_ns), the
# fetch None where the window closed before it; ``gap`` the nanoseconds from
# the end of the run before to this run's start (None for the first)
Step = collections.namedtuple(
    "Step", "batch dispatch run fetch ready prev_ready gap")


def device_runs(events, window):
    """[(start_ns, end_ns, whole)] of the step program's runs on the first
    device, in order; ``whole`` is False for a run the window's edge cut."""
    planes = trace.device_planes(events)
    if not planes:
        return []
    runs = [e for e in events
            if e[1] == MODULES_LINE and e[0] == planes[0]]
    if not runs:
        return []
    step_program = collections.Counter(
        e[2] for e in runs).most_common(1)[0][0]
    w0, w1 = window
    return sorted((e[3], e[3] + e[4], e[3] > w0 + 1 and e[3] + e[4] < w1 - 1)
                  for e in runs if e[2] == step_program)


def dispatches(spans):
    """[(start_ns, end_ns, batch, prev_ready or None)] of the
    ``serve/dispatch`` spans, each with the attrs of the
    ``serve/decode_step`` round it, in order."""
    calls = sorted((s[2], s[2] + s[3]) for s in spans
                   if s[1] == "serve/dispatch")
    out, i = [], 0
    for _thread, _name, start, dur, attrs in sorted(
            (s for s in spans if s[1] == "serve/decode_step"),
            key=lambda s: s[2]):
        while i < len(calls) and calls[i][0] < start:
            i += 1
        if i < len(calls) and calls[i][0] < start + dur \
                and "batch" in attrs:
            out.append(calls[i] + (int(attrs["batch"]),
                                   attrs.get("prev_ready")))
            i += 1
    return out


def fetches(spans):
    """{batch: (start_ns, end_ns, ready)} of the ``serve/fetch`` spans that
    say which step they fetched."""
    return {int(s[4]["batch"]): (s[2], s[2] + s[3], int(s[4]["ready"]))
            for s in spans if s[1] == "serve/fetch"
            and "batch" in s[4] and "ready" in s[4]}


def clock_minima(rows):
    """(min of run start - dispatch start, min of fetch end - run end) over
    the rows, in ns, None where no row has the pair: on one clock neither
    can be negative."""
    return (min((r.run[0] - r.dispatch[0] for r in rows), default=None),
            min((r.fetch[1] - r.run[1] for r in rows
                 if r.fetch is not None), default=None))


def violation_ns(rows):
    """By how much the rows contradict one clock: 0 where none does."""
    return max([0.0] + [-m for m in clock_minima(rows) if m is not None])


def scatter_ns(rows):
    """The median distance of ``fetch end - run end`` from its median: small
    where each run lies beside its own fetch (module docstring)."""
    waits = [r.fetch[1] - r.run[1] for r in rows if r.fetch is not None]
    if not waits:
        return float("inf")
    middle = statistics.median(waits)
    return statistics.median(abs(w - middle) for w in waits)


def _rows(calls, fetched, runs, offset):
    """The table with run j put beside dispatch j + ``offset``."""
    rows, prev_end = [], None
    whole = 0
    for start, end, is_whole in runs:
        if is_whole:
            at = whole + offset
            whole += 1
            if 0 <= at < len(calls):
                d0, d1, batch, prev_ready = calls[at]
                f0, f1, ready = fetched.get(batch, (None, None, None))
                rows.append(Step(
                    batch, (d0, d1), (start, end),
                    None if f0 is None else (f0, f1), ready,
                    None if prev_ready is None else int(prev_ready),
                    None if prev_end is None else start - prev_end))
        prev_end = end
    return rows


def steps(run, events):
    """[Step, ...] of the traced window, or None (module docstring)."""
    if run is None or not events:
        return None
    spans, _ops, window = run
    fetched = fetches(spans)
    calls = dispatches(spans)
    runs = device_runs(events, window)
    n_runs = sum(1 for r in runs if r[2])
    if not fetched or not calls or not n_runs:
        return None
    # the counts must agree up to what the window's edges explain
    tables = (_rows(calls, fetched, runs, o) for o in OFFSETS
              if 0 <= len(calls) - o - n_runs <= TAIL)
    return min((t for t in tables if t), key=scatter_ns, default=None)


def of_run(facts):
    """``(rows, run)`` of the run that ``facts`` describes: its ``steps``
    and the traced segment they were made from, or None where there is no
    table.  What every reader below takes."""
    run = program_trace.of_run(facts)
    rows = steps(run, facts.get("events"))
    return None if rows is None else (rows, run)


# -- the six per-layer readers (``benchmark/metrics/<name>.py``) ------------

def began_in_window(run, name):
    spans, _ops, (w0, w1) = run
    return [s for s in spans if s[1] == name and w0 <= s[2] < w1]


def starved_pct(rows):
    gaps = [r.gap for r in rows if r.gap is not None]
    if not gaps:
        return None
    return 100.0 * sum(g > STARVED_NS for g in gaps) / len(gaps)


def steps_starved_pct(pipeline):
    """Share of the device runs that began more than 50 us after the run
    before them ended: the device had nothing queued."""
    return None if pipeline is None else starved_pct(pipeline[0])


def fetch_waited_pct(pipeline):
    """Share of the ``serve/fetch`` spans that found their step still
    running (``ready`` = 0): the host was there first and the device set
    the pace."""
    if pipeline is None:
        return None
    ready = [s[4]["ready"] for s in began_in_window(
        pipeline[1], "serve/fetch") if "ready" in s[4]]
    if not ready:
        return None
    return 100.0 * sum(1 for r in ready if not r) / len(ready)


def completion_latencies_ns(rows):
    """The time after which the host learns that a step has ended: ``fetch
    end - run end`` of the steps whose fetch found them still running
    (``ready`` = 0), with the device's clock set so that the window's
    earliest run start falls on its dispatch's start.  That takes the
    trace's clock offset out (raw, the median read -0.06 to 2.08 ms over
    five traces of one program; so set, 1.24 to 1.50) and leaves an upper
    bound: the true latency is shorter by the window's fastest launch, some
    0.3-0.5 ms where a step was launched onto an idle device, more where
    none was.  (Not also ``fetch start < run end``, as first planned: that
    compares instants across the clocks.)"""
    earliest = clock_minima(rows)[0]
    return [r.fetch[1] - r.run[1] + earliest for r in rows
            if r.fetch is not None and not r.ready]


def completion_latency_ms(pipeline):
    """Their median."""
    if pipeline is None:
        return None
    waits = completion_latencies_ns(pipeline[0])
    return statistics.median(waits) / 1e6 if waits else None


def weighted_percentile(pairs, q):
    """The smallest value under which ``q`` of the weight lies, of
    (value, weight) pairs."""
    pairs = sorted(p for p in pairs if p[1] > 0)
    total = sum(w for _v, w in pairs)
    seen = 0.0
    for value, weight in pairs:
        seen += weight
        if seen >= q * total:
            return value
    return None


def token_gap_p99_ms(pipeline):
    """The 99th percentile of the gap between two tokens of one request:
    the intervals between the ends of consecutive ``serve/retire`` spans
    that handed out tokens, each weighted by the later one's ``decoded``."""
    if pipeline is None:
        return None
    ends = sorted((s[2] + s[3], s[4]["decoded"]) for s in began_in_window(
        pipeline[1], "serve/retire") if s[4].get("decoded", 0) > 0)
    gap = weighted_percentile(
        [(b[0] - a[0], b[1]) for a, b in zip(ends, ends[1:])], 0.99)
    return None if gap is None else gap / 1e6


def request_ttft_p50_ms(pipeline):
    """Median time to the first token (``ttft_us``) of the requests that
    settled ``ok`` in the window (``serve/request_done``)."""
    if pipeline is None:
        return None
    first = [s[4]["ttft_us"] for s in began_in_window(
        pipeline[1], "serve/request_done")
        if s[4].get("outcome") == "ok" and "ttft_us" in s[4]]
    return statistics.median(first) / 1e3 if first else None


def trace_clock_violation_us(pipeline):
    """By how much the trace's two clocks are shown to disagree: 0 where
    no step contradicts one clock."""
    return None if pipeline is None else violation_ns(pipeline[0]) / 1e3


# -- the summary (PERF.md section 5) ----------------------------------------

def _quartiles(values):
    if len(values) < 2:
        return tuple(values) * 3
    return tuple(statistics.quantiles(values, n=4))


def summary(rows):
    """What PERF.md's table holds, as a dict of plain numbers.  When the
    device ended a step, the next one was *queued* (its run began within
    50 us: the host waited for the device), or its dispatch was under way
    (*raced*: the device idled for less than that dispatch lasted), or had
    not begun (*starved*: it idled for longer).  Durations only, so the
    clocks' offset does not enter; a dispatch enqueues near its end, so a
    step that ended just before the next dispatch began reads as raced.
    ``starved_round_trip_ms`` is durations only too, and is what one step in
    flight puts on the critical path beside the host's own work."""
    after = [r for r in rows if r.gap is not None]
    n = max(len(after), 1)
    queued = sum(1 for r in after if r.gap <= STARVED_NS)
    starved = sum(1 for r in after
                  if r.gap >= r.dispatch[1] - r.dispatch[0]
                  and r.gap > STARVED_NS)
    late = [r.gap for r in after if r.gap > STARVED_NS]
    # of a step launched on an idle device and then waited for: dispatch
    # start to fetch end, less the run itself (launch + completion latency)
    round_trip = [(r.fetch[1] - r.dispatch[0]) - (r.run[1] - r.run[0])
                  for r in after if r.gap > STARVED_NS
                  and r.fetch is not None and not r.ready]
    known = [r.prev_ready for r in rows if r.prev_ready is not None]
    ready = [r.ready for r in rows if r.ready is not None]
    run_ns = [r.run[1] - r.run[0] for r in rows]
    minima = clock_minima(rows)
    return {
        "steps": len(rows),
        "device_run_ms_median": statistics.median(run_ns) / 1e6,
        "next_step_queued_pct": 100.0 * queued / n,
        "ended_during_next_dispatch_pct": 100.0 * (n - queued - starved) / n,
        "ended_before_next_dispatch_pct": 100.0 * starved / n,
        "steps_starved_pct": starved_pct(rows),
        "starved_gap_ms_mean": (sum(late) / len(late) / 1e6 if late
                                else None),
        "gap_seconds": sum(r.gap for r in after) / 1e9,
        "starved_gap_seconds": sum(late) / 1e9,
        "prev_ready_pct": (100.0 * sum(known) / len(known) if known
                           else None),
        "fetch_waited_pct": (100.0 * sum(1 for r in ready if not r)
                             / len(ready) if ready else None),
        "completion_latency_ms_quartiles": tuple(
            v / 1e6 for v in _quartiles(completion_latencies_ns(rows))),
        "starved_round_trip_ms_quartiles": tuple(
            v / 1e6 for v in _quartiles(round_trip)),
        "run_start_minus_dispatch_start_us_min": (
            None if minima[0] is None else minima[0] / 1e3),
        "fetch_end_minus_run_end_us_min": (
            None if minima[1] is None else minima[1] / 1e3),
        "trace_clock_violation_us": violation_ns(rows) / 1e3,
    }


def main(argv):
    if len(argv) > 2:
        print(__doc__.split("\n\n")[-1], file=sys.stderr)
        return 2
    path = argv[1] if len(argv) == 2 else program_trace.TRACE_DIR
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    run = program_trace.load(path)
    if run is None:
        print("no %s span in %s" % (trace.WINDOW_SPAN, path))
        return 1
    events = trace.clip_to_window(
        trace.load_events(path, host_names=(trace.WINDOW_SPAN,)))
    rows = steps(run, events)
    if rows is None:
        print("no step pipeline in %s: no device plane, no `batch` on the "
              "fetches, or dispatches and device runs that do not pair"
              % path)
        return 1
    print("%s\nwindow %.4f s" % (path, (run[2][1] - run[2][0]) / 1e9))
    for key, value in summary(rows).items():
        if isinstance(value, tuple):
            value = " / ".join("%.4f" % v for v in value)
        elif isinstance(value, float):
            value = "%.4f" % value
        print("  %-42s %s" % (key, value))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
