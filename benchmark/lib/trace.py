"""Reduction of a jax profiler trace (``.xplane.pb``) to what the metrics
read: device busy time, device time by operation name, and the longest idle
gaps named by what the host was doing.

``load_events`` is the only function that touches the profiler's file format;
everything else works on its plain output, a list of
``(plane, line, name, start_ns, duration_ns)`` tuples, so the arithmetic is
tested on a small recorded list (``tests/data/``).
"""
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
# the line of a device plane that carries one event per executed operation
OPS_LINE = "XLA Ops"
# the host span the harness puts round the traced segment
WINDOW_SPAN = "bench_window"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return files[-1]


def op_name(event_name):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: libtpu names a
    device event by the whole HLO instruction."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_family(name):
    """``flash_fwd.12`` -> ``flash_fwd``: the instances of one operation."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def load_events(path, host_names=()):
    """Device-plane events of every line, and the host plane's events whose
    name is in ``host_names`` (the benchmark's own TraceAnnotation spans)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    events = []
    host_names = set(host_names)
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name in host_names:
                    events.append((plane.name, line.name, op_name(ev.name),
                                   float(ev.start_ns), float(ev.duration_ns)))
    return events


def window_of(events):
    """(start_ns, end_ns) of the harness's span round the traced segment,
    or None where the trace holds none."""
    spans = [(e[3], e[3] + e[4]) for e in events
             if e[0] == HOST_PLANE and e[2] == WINDOW_SPAN]
    return max(spans, key=lambda w: w[1] - w[0]) if spans else None


def clip_to_window(events):
    """Device events cut to the traced segment: the profiler runs before and
    after it, and a step in flight at either edge counts only by the part
    inside, as the window's seconds do."""
    window = window_of(events)
    if window is None:
        return events
    w0, w1 = window
    out = []
    for e in events:
        if e[0] == HOST_PLANE:
            out.append(e)
            continue
        start, end = max(e[3], w0), min(e[3] + e[4], w1)
        if end > start:
            out.append((e[0], e[1], e[2], start, end - start))
    return out


def device_planes(events):
    return sorted({e[0] for e in events
                   if e[0].startswith(DEVICE_PLANE_PREFIX)})


def op_events(events, plane):
    """One device's executed operations: the ``XLA Ops`` line (libtpu names
    it so); a ``while`` or ``conditional`` wrapper spans its body's
    operations and is left out so that nothing is counted twice."""
    out = [e for e in events if e[0] == plane and e[1] == OPS_LINE]
    return [e for e in out if not _is_wrapper(e[2])]


def _is_wrapper(name):
    return op_family(name) in ("while", "conditional", "call")


def union_seconds(intervals):
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_seconds(events):
    """Seconds in which an operation ran, averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    busy = [union_seconds([(e[3], e[3] + e[4]) for e in op_events(events, p)])
            for p in planes]
    return sum(busy) / len(busy) / 1e9


def seconds_by_name(events, contains):
    """Summed device seconds of the operations whose name holds
    ``contains``, averaged over the device planes; None where none ran."""
    planes = device_planes(events)
    per_plane = []
    for p in planes:
        hit = [e[4] for e in op_events(events, p) if contains in e[2]]
        if hit:
            per_plane.append(sum(hit))
    if not per_plane:
        return None
    return sum(per_plane) / len(planes) / 1e9


def top_ops(events, n=10):
    """[[operation, seconds], ...] of the first device plane, largest first,
    the instances of one operation (``fusion.1``, ``fusion.2``) together."""
    planes = device_planes(events)
    if not planes:
        return []
    tally = {}
    for e in op_events(events, planes[0]):
        family = op_family(e[2])
        tally[family] = tally.get(family, 0.0) + e[4]
    rows = sorted(tally.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def idle_gaps(events, n=10):
    """The first device's idle seconds, summed by the benchmark's host span
    that covered the gap's midpoint (``(none)`` where no span did)."""
    planes = device_planes(events)
    if not planes:
        return []
    ops = sorted((e[3], e[3] + e[4]) for e in op_events(events, planes[0]))
    host = [(e[3], e[3] + e[4], e[2]) for e in events
            if e[0] == HOST_PLANE and e[2] != WINDOW_SPAN]
    tally = {}
    end = None
    for s, e in ops:
        if end is not None and s > end:
            mid = (s + end) / 2
            name = next((h[2] for h in host if h[0] <= mid <= h[1]),
                        "(none)")
            tally[name] = tally.get(name, 0.0) + (s - end)
        end = e if end is None else max(end, e)
    rows = sorted(tally.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]
