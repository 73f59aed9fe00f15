"""Collective-traffic accounting from compiled HLO.

Gives the scaling story quantitative teeth: parse a compiled step's HLO
for collective instructions, sum their payload bytes, and compare the
data-parallel gradient all-reduce against the analytic ring model
(bytes_on_wire_per_device = 2 * (n-1)/n * payload) that linear-scaling
claims rest on.  Reference anchor: the reference's measured ~90% linear
scaling at 256 GPUs rode exactly this ring-allreduce cost model
(example/image-classification README); on TPU the same math rides ICI.
"""
from __future__ import annotations

import re
import threading
import time
from collections import deque

# every collective HLO op we account for
_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                "collective-permute")

# ---------------------------------------------------------------------------
# Runtime collective trail.  The HLO accounting above is static; this is the
# dynamic half: every collective/barrier entry point records a completion
# event here, so when the watchdog (resilience/watchdog.py) fires on a hang
# the post-mortem can say which collective LAST finished — i.e. where in the
# program the ranks diverged.  Bounded deque, thread-safe, ~O(ns) per event.
# ---------------------------------------------------------------------------

_RUNTIME_LOG: "deque" = deque(maxlen=128)
_RUNTIME_LOCK = threading.Lock()


def record_collective(kind: str, tag: str = "", step=None, bytes=None):
    """Note a completed collective (``kind`` = psum/barrier/ppermute/
    all_to_all/..., ``tag`` = call-site label, ``bytes`` = operand
    payload when the entry point knows it).

    Besides the bounded forensic trail, each record fans out into the
    telemetry layer when armed: a ``parallel.collectives`` counter
    (labeled by kind) + ``parallel.collective_bytes``, and a zero-width
    marker in the merged Chrome trace so collective completions line up
    against the span timeline."""
    now = time.time()
    with _RUNTIME_LOCK:
        _RUNTIME_LOG.append({"time": now, "kind": kind,
                             "tag": tag, "step": step, "bytes": bytes})
    from .. import telemetry
    if telemetry.is_armed():
        telemetry.count("parallel.collectives", kind=kind)
        if bytes:
            telemetry.count("parallel.collective_bytes", float(bytes),
                            kind=kind)
    from .. import profiler
    if profiler.is_running():
        args = {"kind": kind, "tag": tag}
        if step is not None:
            args["step"] = step
        if bytes is not None:
            args["bytes"] = int(bytes)
        profiler.record_event("collective/%s" % kind,
                              time.perf_counter() * 1e6, 0.0,
                              cat="collective", args=args)


def last_collective():
    """The most recent completed-collective event, or None."""
    with _RUNTIME_LOCK:
        return dict(_RUNTIME_LOG[-1]) if _RUNTIME_LOG else None


def collective_log(n: int = None):
    """The newest ``n`` (default: all retained) collective events."""
    with _RUNTIME_LOCK:
        items = [dict(e) for e in _RUNTIME_LOG]
    return items[-n:] if n else items


def clear_collective_log():
    with _RUNTIME_LOCK:
        _RUNTIME_LOG.clear()


# ---------------------------------------------------------------------------
# replica-group parsing + mesh-axis attribution
# ---------------------------------------------------------------------------

_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_GROUPS_BRACE_RE = re.compile(
    r"replica_groups=\{(\{[\d, ]*\}(?:, *\{[\d, ]*\})*)\}")
_PAIRS_RE = re.compile(
    r"source_target_pairs=\{(\{[\d, ]*\}(?:, *\{[\d, ]*\})*)\}")


def parse_replica_groups(attrs_text):
    """``replica_groups`` from an HLO attr string, both syntaxes: the
    explicit brace form ``{{0,4},{1,5}}`` and the iota form
    ``[ngroups,size]<=[dims](T(perm))``.  Returns a list of id tuples or
    None when the instruction carries no groups."""
    m = _GROUPS_IOTA_RE.search(attrs_text)
    if m:
        import numpy as _np
        ngroups, gsize = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = _np.arange(int(_np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(",")])
        ids = ids.reshape(ngroups, gsize)
        return [tuple(int(x) for x in row) for row in ids]
    m = _GROUPS_BRACE_RE.search(attrs_text)
    if m:
        return [tuple(int(x) for x in grp.split(",") if x.strip())
                for grp in re.findall(r"\{([\d, ]*)\}", m.group(1))]
    return None


class AxisLabeler:
    """Attribute a collective's replica groups to the mesh axis (or axis
    combination) they span, so the audit can say which bytes are dp
    traffic vs tp vs ep — the 'per-axis byte accounting' a composed
    dp×tp×pp program needs to be debuggable at all."""

    def __init__(self, mesh):
        self.mesh = getattr(mesh, "mesh", mesh)   # MeshSpec or Mesh
        self._partitions = None

    def _axis_partitions(self):
        """[(label, frozenset-of-frozensets)] for every non-empty subset
        of size>1 axes, smallest subsets first (a dp group must label
        'dp', not 'dp×pp-with-trivial-pp')."""
        if self._partitions is not None:
            return self._partitions
        import itertools
        import numpy as _np
        mesh = self.mesh
        ids = _np.vectorize(lambda d: d.id)(mesh.devices)
        axes = list(mesh.axis_names)
        names = [a for a in axes if mesh.shape[a] > 1]
        parts = []
        for r in range(1, len(names) + 1):
            for sub in itertools.combinations(names, r):
                perm = [i for i, a in enumerate(axes) if a not in sub] + \
                       [i for i, a in enumerate(axes) if a in sub]
                gsize = 1
                for a in sub:
                    gsize *= mesh.shape[a]
                arr = ids.transpose(perm).reshape(-1, gsize)
                key = frozenset(frozenset(int(x) for x in row)
                                for row in arr)
                parts.append(("x".join(sub), key))
        self._partitions = parts
        return parts

    def _all_axes_label(self):
        mesh = self.mesh
        names = [a for a in mesh.axis_names if mesh.shape[a] > 1]
        return "x".join(names) if names else "self"

    def label_groups(self, groups):
        if groups is None:
            return "unmapped"
        key = frozenset(frozenset(g) for g in groups if len(g) > 1)
        if not key:
            return "self"
        for label, part in self._axis_partitions():
            if part == key:
                return label
        return "unmapped"

    def label_pairs(self, pairs):
        """A collective-permute's source_target_pairs belong to the
        smallest axis subset whose device partition keeps every pair
        within one group (the ring axis)."""
        if not pairs:
            return "unmapped"
        for label, part in self._axis_partitions():
            if all(any(s in grp and t in grp for grp in part)
                   for s, t in pairs):
                return label
        return "unmapped"

    def label(self, ins):
        groups = parse_replica_groups(ins.attrs)
        if groups is not None:
            if not groups or all(not g for g in groups):
                # empty groups = every participant
                return self._all_axes_label()
            return self.label_groups(groups)
        m = _PAIRS_RE.search(ins.attrs)
        if m:
            pairs = [tuple(int(x) for x in grp.split(","))
                     for grp in re.findall(r"\{([\d, ]*)\}", m.group(1))
                     if grp.strip()]
            return self.label_pairs([p for p in pairs if len(p) == 2])
        return "unmapped"


def collective_accounting(hlo_text, mesh=None):
    """Payload bytes + instruction count per collective kind.

    Returns ``{kind: {"count": int, "bytes": int, ...}}`` over non-fused,
    non-async-duplicate instructions ('-start' variants counted once via
    their operand shapes, '-done' skipped).  Payload conventions: sync
    ops report their result bytes, async ``-start`` their operand bytes,
    reduce-scatter therefore the (1/group) shard.  An instruction is
    counted as the opcode the compiler emitted: an all-reduce whose only
    consumer slices out this partition's shard moves a full all-reduce on
    the wire and is reported as one.

    With ``mesh`` given, every kind carries a ``by_axis`` breakdown
    mapping the instruction's replica groups (or ppermute pairs) onto
    the mesh axes — dp vs tp vs ep traffic becomes directly
    attributable in dryrun output.
    """
    from ..analysis.costmodel import iter_instructions
    labeler = AxisLabeler(mesh) if mesh is not None else None
    out = {}
    for ins in iter_instructions(hlo_text):
        op = ins.opcode
        is_start = op.endswith("-start")
        kind = op[:-len("-start")] if is_start else op
        if kind not in _COLLECTIVES or op.endswith("-done"):
            continue
        # async -start result types bundle (operand, result[, scratch])
        # shapes; the operand shapes are what the collective is fed
        # (asymmetric all-gather/reduce-scatter fix)
        payload = ins.operand_bytes if is_start else ins.result_bytes
        slot = out.setdefault(kind, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += payload
        if labeler is not None:
            axis = labeler.label(ins)
            ba = slot.setdefault("by_axis", {}).setdefault(
                axis, {"count": 0, "bytes": 0})
            ba["count"] += 1
            ba["bytes"] += payload
    return out


def ring_allreduce_wire_bytes(payload_bytes, n_devices):
    """Per-device bytes on the wire for a ring all-reduce of ``payload``."""
    return 2 * (n_devices - 1) * payload_bytes // max(1, n_devices)


def collective_wire_bytes(kind, payload_bytes, n_devices):
    """Per-device wire bytes for one collective, per the payload
    conventions of :func:`collective_accounting` (reduce-scatter payload
    is the 1/n output shard; sync all-gather payload is the gathered
    result): ring models in all cases."""
    n = max(1, n_devices)
    if kind == "all-reduce":
        return ring_allreduce_wire_bytes(payload_bytes, n)
    if kind == "reduce-scatter":
        return (n - 1) * payload_bytes
    if kind == "all-gather":
        return (n - 1) * payload_bytes // n
    return payload_bytes


def zero_update_model_bytes(shardable_bytes, residual_bytes):
    """Analytic per-step collective PAYLOADS of the ZeRO sharded weight
    update (the audit-side model the dryrun holds measurements against),
    as the installed compilers emit it: every gradient is all-reduced in
    full and each device then slices out the shard it owns (neither
    XLA:CPU nor libtpu 0.0.34 for v5e turns that pair into a
    reduce-scatter — PERF.md, "ZeRO on the wire"), and the updated
    weights of the shardable params all-gather back whole."""
    return {"all-reduce": shardable_bytes + residual_bytes,
            "all-gather": shardable_bytes}


def hierarchical_allreduce_model_bytes(payload_bytes, islands, per_island,
                                       elem_bytes=4):
    """Analytic per-device collective payloads of the two-tier
    hierarchical all-reduce (parallel/hierarchy.py) of a ``payload``-byte
    tensor on an ``islands`` x ``per_island`` mesh, in the payload
    conventions of :func:`collective_accounting`:

    * ``reduce-scatter`` — in-island (fast tier), payload = the 1/k
      output shard;
    * ``all-reduce`` — cross-island (slow tier), on that 1/k shard;
    * ``all-gather`` — in-island (fast tier), payload = the gathered
      full tensor.

    Plus the two wire numbers the "≪ flat ring" claim is audited with:
    ``slow_wire`` — per-designated-rank bytes crossing the slow tier
    (ring all-reduce of the shard over the m islands) — and
    ``flat_wire`` — what a flat ring over all m*k devices would push
    through its slow-tier crossing links (2(N-1)/N * payload, since a
    flat ring's full per-link traffic rides every link, slow ones
    included)."""
    m = max(1, islands)
    k = max(1, per_island)
    # the scatter pads in ELEMENTS to a multiple of k, so the shard is
    # ceil(elems/k) elements, not ceil(bytes/k) bytes
    elems = -(-payload_bytes // elem_bytes)
    shard = -(-elems // k) * elem_bytes
    return {
        "reduce-scatter": shard,
        "all-reduce": shard,
        "all-gather": shard * k,
        "slow_wire": ring_allreduce_wire_bytes(shard, m),
        "flat_wire": ring_allreduce_wire_bytes(payload_bytes, m * k),
    }


def grad_payload_bytes(params, grad_dtype_bytes=4):
    """Analytic dp all-reduce payload: every gradient, in f32."""
    total = 0
    for p in params:
        n = 1
        for d in p.shape:
            n *= int(d)
        total += n * grad_dtype_bytes
    return total


def audit_report(tag, hlo_text, n_devices, params=None, ring_n=None,
                 mesh=None, zero_model=None, hier_model=None):
    """Format (and return) one accounting line comparing HLO collective
    payloads with the analytic ring model.

    ``ring_n`` is the all-reduce REPLICA-GROUP size (the dp extent) —
    on a dp x tp mesh the gradient ring runs over dp only, not over all
    n_devices.  Pass ``params`` only when the HLO payloads are global
    (pure-dp): with tp the post-SPMD HLO reports per-shard payloads and
    a global-params model would be ~tp x off, so the ratio is skipped.
    ``mesh`` adds the per-axis byte breakdown (dp/tp/sp/ep/pp traffic
    attributed from replica groups).  ``zero_model`` — the dict from
    :func:`zero_update_model_bytes` — swaps the plain grad-payload
    comparison for the ZeRO all-reduce + all-gather model.
    ``hier_model`` — from :func:`hierarchical_allreduce_model_bytes` —
    appends the two-tier comparison: per-kind measured/model payloads
    plus the slow-tier wire bytes against the flat-ring baseline.
    """
    ring_n = ring_n or n_devices
    acct = collective_accounting(hlo_text, mesh=mesh)
    parts = []
    for kind in sorted(acct):
        info = acct[kind]
        wire = collective_wire_bytes(kind, info["bytes"], ring_n)
        parts.append("%s: %d ops, %.2f MB payload, %.2f MB/device on "
                     "wire" % (kind, info["count"], info["bytes"] / 1e6,
                               wire / 1e6))
    text = "collectives[%s, n=%d, ring=%d] " % (tag, n_devices, ring_n) + \
        ("; ".join(parts) if parts else "none")
    if mesh is not None:
        by_axis = {}
        for kind, info in acct.items():
            for axis, slot in (info.get("by_axis") or {}).items():
                by_axis[axis] = by_axis.get(axis, 0) + slot["bytes"]
        if by_axis:
            text += " | by-axis " + ", ".join(
                "%s: %.2f MB" % (a, b / 1e6)
                for a, b in sorted(by_axis.items()))
    if zero_model is not None:
        model = sum(zero_model.values())
        measured = sum(acct.get(k, {}).get("bytes", 0)
                       for k in zero_model)
        text += (" | analytic ZeRO payload AR %.2f + AG %.2f MB"
                 " (measured/model = %.2f)"
                 % (zero_model.get("all-reduce", 0) / 1e6,
                    zero_model.get("all-gather", 0) / 1e6,
                    measured / model if model else float("nan")))
    if hier_model is not None:
        kinds = ("reduce-scatter", "all-reduce", "all-gather")
        model = sum(hier_model.get(kd, 0) for kd in kinds)
        measured = sum(acct.get(kd, {}).get("bytes", 0) for kd in kinds)
        slow, flat = hier_model.get("slow_wire", 0), \
            hier_model.get("flat_wire", 0)
        text += (" | analytic 2-tier payload RS %.2f + slowAR %.2f + AG "
                 "%.2f MB (measured/model = %.2f); slow-tier wire %.2f MB"
                 "/rank vs %.2f MB flat ring (%.1fx less)"
                 % (hier_model.get("reduce-scatter", 0) / 1e6,
                    hier_model.get("all-reduce", 0) / 1e6,
                    hier_model.get("all-gather", 0) / 1e6,
                    measured / model if model else float("nan"),
                    slow / 1e6, flat / 1e6,
                    flat / slow if slow else float("nan")))
    elif params is not None:
        model = grad_payload_bytes(params)
        measured = acct.get("all-reduce", {}).get("bytes", 0)
        text += " | analytic grad payload %.2f MB (measured/model = %.2f)" \
            % (model / 1e6, measured / model if model else float("nan"))
    if acct:
        # collective/compute overlap: the standing instrument behind the
        # "collectives overlap compute, spans prove it" perf criterion
        # (telemetry/perf.py folds the same number into attribution
        # reports)
        from ..analysis import costmodel
        ov = costmodel.collective_compute_overlap(hlo_text)
        if ov["overlap_pct"] is not None:
            text += " | collective/compute overlap %.1f%% " \
                "(%d async, %d sync of which %d pipelined)" % (
                    ov["overlap_pct"], ov["async_ops"], ov["sync_ops"],
                    ov.get("pipelined_ops", 0))
    return text, acct
