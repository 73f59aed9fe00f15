#!/usr/bin/env python3
"""What the program itself wrote into a jax profiler trace: its host spans
(every ``mxnet_tpu.telemetry.span`` is a ``jax.profiler.TraceAnnotation``)
and the ``mx.*`` scopes (``jax.named_scope``) that the device's operations
carry, both on the trace's one clock.

``read_xplane`` is the only function that touches the file format.  It reads
the ``.xplane.pb`` by its wire format because the scope lives where
``jax.profiler.ProfileData`` does not look: libtpu puts an operation's HLO
``op_name`` path (``jit(step)/transpose(jvp(mx.BatchNorm.bn1))/mul:``) into
the stat ``tf_op`` of the *event metadata*, and ``ProfileData`` shows an
event's own stats only (probed on the v5e, PR 26).  Everything else here is
arithmetic on its plain output, tested on a small recorded list
(``tests/data/program_trace.json``):

    spans: (thread as "name#id", name, start_ns, duration_ns, attrs)
    ops:   (plane, operation, op_name path or "", start_ns, duration_ns)

Run as ``python3 benchmark/lib/program_trace.py <trace dir or .xplane.pb>``
it prints device seconds by scope and idle seconds by innermost program
span: the tables of PERF.md section 5.
"""
import functools
import os
import re
import struct
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import trace  # noqa: E402

# where ``run.py --trace 1`` leaves its trace (``harness.trace_dir`` names the
# same directory and empties it, so the readers must not call it)
TRACE_DIR = os.path.join(ROOT, ".cache", "bench_trace")
# the program's span families (docs/observability.md); the harness's window
SPAN_PREFIXES = ("serve/", "train/", "compile/")
SCOPE = re.compile(r"mx\.[^/():]+")
# a Pallas kernel's operation is named after its call (``jvp_flash_fwd_``),
# scope or not; the names ``flash_roofline`` and ``decode_attn_roofline`` read
KERNELS = ("flash_", "decode_attn")
ENGINE_HOST_SPANS = ("serve/admit", "serve/build", "serve/dispatch",
                     "serve/retire")
NONE = "(none)"


# -- the file format --------------------------------------------------------

def read_xplane(path):
    """``(spans, ops)`` of one ``.xplane.pb``: the host plane's events named
    like a program span or like the harness's window, and every device
    plane's ``XLA Ops`` events with the ``tf_op`` stat of their metadata.

    Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``:
    XSpace.planes=1; XPlane name=2 lines=3 event_metadata=4 stat_metadata=5;
    XLine id=1 name=2 timestamp_ns=3 events=4; XEvent metadata_id=1 offset_ps=2
    duration_ps=3 stats=4; XStat metadata_id=1 double=2 uint64=3 int64=4
    str=5 bytes=6 ref=7; XEventMetadata name=2 stats=5; XStatMetadata
    name=2; a map entry is key=1 value=2."""
    with open(path, "rb") as f:
        buf = f.read()

    def varint(i):
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            if b < 0x80:
                return value, i
            shift += 7

    def fields(start, end):
        """(field number, value) pairs of one message: an int for a varint,
        a (start, end) pair for a length-delimited field, a float for the
        fixed ones."""
        i = start
        while i < end:
            key, i = varint(i)
            wire = key & 7
            if wire == 0:
                value, i = varint(i)
            elif wire == 2:
                size, i = varint(i)
                value = (i, i + size)
                i += size
            elif wire == 1:
                value = struct.unpack_from("<d", buf, i)[0]
                i += 8
            elif wire == 5:
                value = struct.unpack_from("<f", buf, i)[0]
                i += 4
            else:
                raise ValueError("wire type %d in %s" % (wire, path))
            yield key >> 3, value

    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    def signed(v):
        return v - (1 << 64) if v >= 1 << 63 else v

    def map_entry(span):
        key = value = None
        for no, v in fields(*span):
            if no == 1:
                key = v
            elif no == 2:
                value = v
        return key, value

    def stats_of(pairs, stat_names):
        out = {}
        for span in pairs:
            name = value = None
            for no, v in fields(*span):
                if no == 1:
                    name = stat_names.get(v)
                elif no in (2, 3):
                    value = v
                elif no == 4:
                    value = signed(v)
                elif no == 5:
                    value = text(v)
                elif no == 7:
                    value = stat_names.get(v, "")
            if name is not None and value is not None:
                out[name] = value
        return out

    spans, ops = [], []
    for no, plane in fields(0, len(buf)):
        if no != 1:
            continue
        parts = list(fields(*plane))
        name = next((text(v) for n, v in parts if n == 2), "")
        device = name.startswith(trace.DEVICE_PLANE_PREFIX)
        if not device and name != trace.HOST_PLANE:
            continue
        stat_names = {}
        for n, v in parts:
            if n == 5:
                key, meta = map_entry(v)
                stat_names[key] = next(
                    (text(x) for m, x in fields(*meta) if m == 2), "")
        events = {}             # metadata id -> (name, op_name path)
        for n, v in parts:
            if n != 4:
                continue
            key, meta = map_entry(v)
            ev_name, ev_stats = "", []
            for m, x in fields(*meta):
                if m == 2:
                    ev_name = text(x)
                elif m == 5:
                    ev_stats.append(x)
            if device:
                events[key] = (trace.op_name(ev_name),
                               stats_of(ev_stats, stat_names)
                               .get("tf_op", ""))
            elif ev_name == trace.WINDOW_SPAN \
                    or ev_name.startswith(SPAN_PREFIXES):
                events[key] = (ev_name, "")
        for n, v in parts:
            if n != 3:
                continue
            line = list(fields(*v))
            line_name = next((text(x) for m, x in line if m == 2), "")
            if device and line_name != trace.OPS_LINE:
                continue
            # threads share names (every Python thread's line is "python")
            thread = "%s#%d" % (line_name,
                                next((x for m, x in line if m == 1), 0))
            t0 = next((signed(x) for m, x in line if m == 3), 0)
            for m, x in line:
                if m != 4:
                    continue
                meta_id = offset_ps = duration_ps = 0
                ev_stats = []
                for k, y in fields(*x):
                    if k == 1:
                        meta_id = y
                        if y not in events:     # not ours: skip its stats
                            break
                    elif k == 2:
                        offset_ps = signed(y)
                    elif k == 3:
                        duration_ps = signed(y)
                    elif k == 4:
                        ev_stats.append(y)
                if meta_id not in events:
                    continue
                ev_name, path_ = events[meta_id]
                start, dur = t0 + offset_ps / 1e3, duration_ps / 1e3
                if device:
                    ops.append((name, ev_name, path_, start, dur))
                else:
                    spans.append((thread, ev_name, start, dur,
                                  stats_of(ev_stats, stat_names)))
    return spans, ops


@functools.lru_cache(maxsize=1)
def _run_once(path, _mtime_ns, _size):
    return in_window(*read_xplane(path))


def load(path):
    """``in_window(*read_xplane(path))``, worked out once per file however
    many readers ask."""
    st = os.stat(path)
    return _run_once(path, st.st_mtime_ns, st.st_size)


def of_run(facts):
    """The traced segment of the run that ``facts`` describes, cut to the
    harness's window: ``(spans, ops, (start_ns, end_ns))``, or None where
    the run was not traced, left no trace on disk, or held no window."""
    if not facts.get("events"):
        return None
    try:
        return load(trace.find_xplane(TRACE_DIR))
    except FileNotFoundError:
        return None


# -- arithmetic on the plain lists ------------------------------------------

def in_window(spans, ops):
    """Spans that touch the harness's window (whole, so a count of them is a
    count of iterations) and operations cut to it, as ``lib/trace.py`` cuts
    them; None where the trace holds no window."""
    windows = [(s[2], s[2] + s[3]) for s in spans
               if s[1] == trace.WINDOW_SPAN]
    if not windows:
        return None
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    kept = [s for s in spans if s[1] != trace.WINDOW_SPAN
            and s[2] < w1 and s[2] + s[3] > w0]
    cut = []
    for plane, op, path, start, dur in ops:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a and not trace._is_wrapper(op):
            cut.append((plane, op, path, a, b - a))
    return kept, cut, (w0, w1)


@functools.lru_cache(maxsize=None)      # a step's few thousand paths recur
def scope_of(path):
    """``jit(step)/transpose(jvp(mx.BatchNorm.bn1))/mul:`` ->
    ``mx.BatchNorm.bn1`` (the innermost, where scopes nest); None where the
    path holds none."""
    found = SCOPE.findall(path)
    return found[-1] if found else None


def seconds_by_scope(ops):
    """{scope or kernel or ``(none)``: device seconds}, all device planes
    together.  A Pallas kernel outside every scope goes by its own name."""
    out = {}
    for _plane, op, path, _start, dur in ops:
        key = scope_of(path)
        if key is None and any(k in op for k in KERNELS):
            key = trace.op_family(op)   # ``jvp_flash_fwd_.3``: by its name
        if key is None:
            key = NONE
        out[key] = out.get(key, 0.0) + dur / 1e9
    return out


def scope_share_pct(run, wanted):
    """100 x device seconds under the scopes that ``wanted(scope)`` picks
    over all device seconds in the window; None where the trace holds no
    ``mx.`` scope at all or none that is wanted."""
    if run is None:
        return None
    by_scope = seconds_by_scope(run[1])
    if not any(k.startswith("mx.") for k in by_scope):
        return None
    hit = sum(v for k, v in by_scope.items() if wanted(k))
    total = sum(by_scope.values())
    return 100.0 * hit / total if hit > 0 and total > 0 else None


def scoped_pct(run):
    """Share of the device's busy seconds that carry a name the program
    chose: any ``mx.`` scope, or a Pallas kernel's own name."""
    return scope_share_pct(run, lambda k: k != NONE)


def program_spans(spans):
    return [s for s in spans if s[1].startswith(SPAN_PREFIXES)]


def innermost_segments(spans):
    """The timeline cut at every program span's edge: [(start, end, name)]
    with each piece named by the innermost span that covers it, which is the
    one opened last, on whatever thread."""
    todo = sorted((s[2], s[2] + s[3], s[1]) for s in program_spans(spans))
    edges = sorted({t[0] for t in todo} | {t[1] for t in todo})
    out, active, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(todo) and todo[i][0] <= a:
            active.append(todo[i])
            i += 1
        active = [t for t in active if t[1] > a]
        if active:
            out.append((a, b, max(active)[2]))
    return out


def idle_seconds_by_span(run):
    """The first device's idle seconds in the window, each gap shared out
    among the innermost program spans it overlaps and ``(none)`` for the
    rest: {name: seconds}.  The window's edges count as gaps, so the total
    is the window less the busy time, as ``device_idle_pct`` has it.  (By
    overlap and not by the gap's midpoint, as ``lib/trace.idle_gaps`` has
    it: the gap between two decode steps spans five of the engine's spans.)"""
    spans, ops, (w0, w1) = run
    planes = sorted({o[0] for o in ops})
    if not planes:
        return {}
    busy = sorted((o[3], o[3] + o[4]) for o in ops if o[0] == planes[0])
    segments = innermost_segments(spans)
    out, end, j = {}, w0, 0
    for s, e in busy + [(w1, w1)]:
        if s > end:                                 # the gap (end, s)
            left = s - end
            while j < len(segments) and segments[j][1] <= end:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < s:
                a, b, name = segments[k]
                part = min(b, s) - max(a, end)
                out[name] = out.get(name, 0.0) + part / 1e9
                left -= part
                k += 1
            if left > 0:
                out[NONE] = out.get(NONE, 0.0) + left / 1e9
        end = max(end, e)
    return out


def idle_named_pct(run):
    """Share of the device's idle seconds that overlap a program span;
    None where the trace holds no program span or the device never idled."""
    if run is None or not program_spans(run[0]):
        return None
    idle = idle_seconds_by_span(run)
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * (total - idle.get(NONE, 0.0)) / total


def engine_host_ms(run):
    """Host milliseconds a decode step outside the wait for the device:
    ``serve/admit`` + ``build`` + ``dispatch`` + ``retire`` of the
    iterations that began in the window, over its ``serve/decode_step``s."""
    if run is None:
        return None
    spans, _ops, (w0, w1) = run
    began = [s for s in spans if w0 <= s[2] < w1]
    steps = sum(1 for s in began if s[1] == "serve/decode_step")
    if not steps:
        return None
    host = sum(s[3] for s in began if s[1] in ENGINE_HOST_SPANS)
    return host / 1e6 / steps


def span_table(run):
    """[(name, count, seconds)] of the program spans that began in the
    window, largest first."""
    spans, _ops, (w0, w1) = run
    tally = {}
    for s in program_spans(spans):
        if w0 <= s[2] < w1:
            n, sec = tally.get(s[1], (0, 0.0))
            tally[s[1]] = (n + 1, sec + s[3] / 1e9)
    return sorted(((k, n, sec) for k, (n, sec) in tally.items()),
                  key=lambda r: -r[2])


def main(argv):
    if len(argv) != 2:
        print(__doc__.split("\n\n")[-1], file=sys.stderr)
        return 2
    path = argv[1]
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    run = load(path)
    if run is None:
        print("no %s span in %s" % (trace.WINDOW_SPAN, path))
        return 1
    spans, ops, (w0, w1) = run
    window = (w1 - w0) / 1e9
    busy = sum(o[4] for o in ops) / 1e9
    print("%s\nwindow %.4f s, device operations %.4f s (%d events)"
          % (path, window, busy, len(ops)))
    by_scope = seconds_by_scope(ops)
    by_kind = {}        # the executor's mx.<OpType>.<node> by operator
    for name, sec in by_scope.items():
        parts = name.split(".")
        if len(parts) > 2 and parts[1] != "decode":
            name = ".".join(parts[:2]) + ".*"
        by_kind[name] = by_kind.get(name, 0.0) + sec
    for title, table in (("scope, a graph's nodes by operator", by_kind),
                         ("scope", by_scope)):
        if table is by_scope and len(by_kind) == len(by_scope):
            continue
        rows = sorted(table.items(), key=lambda kv: -kv[1])
        print("\ndevice seconds by %s" % title)
        for name, sec in rows[:16]:
            print("  %-40s %9.4f  %5.1f%%" % (name, sec, 100 * sec / busy))
        if len(rows) > 16:
            rest = sum(sec for _n, sec in rows[16:])
            print("  %-40s %9.4f  %5.1f%%" % ("(%d more)" % (len(rows) - 16),
                                              rest, 100 * rest / busy))
    print("\ndevice seconds outside every scope, by operation and path")
    loose = {}
    for _plane, op, path_, _start, dur in ops:
        if scope_of(path_) is None and not any(k in op for k in KERNELS):
            key = "%s  %s" % (trace.op_family(op), path_ or "-")
            loose[key] = loose.get(key, 0.0) + dur / 1e9
    for name, sec in sorted(loose.items(), key=lambda kv: -kv[1])[:8]:
        print("  %-40s %9.4f  %5.1f%%" % (name, sec, 100 * sec / busy))
    idle = idle_seconds_by_span(run)
    total = sum(idle.values())
    print("\nidle seconds of the first device by innermost program span "
          "(%.4f s)" % total)
    for name, sec in sorted(idle.items(), key=lambda kv: -kv[1]):
        print("  %-40s %9.4f  %5.1f%%" % (name, sec, 100 * sec / total))
    print("\nprogram spans that began in the window")
    for name, n, sec in span_table(run):
        print("  %-40s %6d x %9.3f ms = %9.4f s"
              % (name, n, 1e3 * sec / n, sec))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
