"""Graph checker: jaxpr-level SPMD/perf lint (pre-flight Engine 1).

Compiler-style analysis passes over the *traced* program — the spirit of
TVM's graph-level passes (arXiv:1802.04799) applied to correctness: trace
any jittable program (a ShardedTrainer step, a Module forward/backward,
the ring/pipeline/moe entry points) to a ClosedJaxpr and run rule passes
over it.  Everything here is static — no device execution, no compile —
so a mismatched collective schedule is rejected at trace time instead of
burning a pod launch before the PR-2 watchdog turns the hang into a
post-mortem.

Rule catalog (docs/static-analysis.md):

========  =======================  ========  ==================================
id        name                     severity  what it catches
========  =======================  ========  ==================================
GC101     collective-axis-unknown  error     collective over an axis name the
                                             mesh does not define
GC102     cond-divergent-          error     `lax.cond` branches with different
          collectives                        collective schedules — ranks that
                                             take different branches deadlock
GC103     while-collective         warning   collective inside `lax.while_loop`
                                             whose trip count is data-dependent
                                             (rank-divergent counts desync)
GC104     ppermute-bad-perm        error     ppermute perm that is not a
                                             partial bijection / out of range
GC105     axis-groups-asymmetric   error     axis_index_groups that do not
                                             partition the axis into equal
                                             disjoint groups
GC106     collective-in-async-     error     collective primitive inside a
          step                               program contracted to be
                                             collective-free (the dist_async
                                             PS worker step: nothing in it may
                                             put a peer on this rank's
                                             critical path)
GC201     replicated-large-array   warning   large state fully replicated on a
                                             model-parallel mesh
GC202     missing-donation         warning   grad/optimizer buffers not donated
                                             (2x peak HBM)
GC203     reshard-chain            warning   chained sharding constraints that
                                             bounce one value between layouts
                                             on the hot path
GC301     bf16-upcast-compute      warning   bf16 values upcast to f32 and fed
                                             straight into dot/conv (silent 2x
                                             FLOP cost on the MXU)
GC302     weak-type-input          warning   weak-typed scalar inputs that
                                             fragment the jit cache
GC304     collectives-serialized   warning   multi-device program moving real
                                             collective payload with ZERO
                                             compute/transfer overlap: no
                                             async -start/-done pair hides
                                             compute and every sync collective
                                             sits on the critical path between
                                             its producers and consumers (the
                                             PR-6 overlap instrument,
                                             costmodel.collective_compute_
                                             overlap, is the oracle)
GC306     densified-embedding-     warning   a program that contains a routed
          grad                               sharded-embedding lookup (all-to-
                                             all present) yet moves full-table-
                                             sized gradient bytes through ONE
                                             dense all-reduce / all-gather —
                                             the "you densified your embedding
                                             grad" footgun: wire bytes scale
                                             with table size instead of
                                             touched rows
GC401     static-float-attr        warning   per-step float attr (lr/wd/...)
                                             reaching an op as a STATIC jit
                                             key -> recompile every step
GC402     registry-dynamic-gap     warning   registered op schema declares a
                                             per-step param outside its
                                             dynamic_params mechanism
GC403     unhashable-attr          error     op attrs that cannot be hashed
                                             into a jit cache key
GC307     decode-retrace           warning   a decode-shaped program (single-
                                             query attention + in-place cache
                                             write) whose trace CHANGES across
                                             step / sequence-length / batch-
                                             membership changes — the
                                             recompile-per-token trap: every
                                             generated token pays a fresh XLA
                                             compile
GC501     hbm-over-capacity        error     predicted peak HBM (costmodel
                                             state/batch accounting +
                                             ``memory_analysis`` temp bytes)
                                             exceeds per-device capacity —
                                             refused BEFORE dispatch instead
                                             of an opaque RESOURCE_EXHAUSTED
========  =======================  ========  ==================================

The per-step attr names behind GC401/GC402 are the scheduled-hyperparam
set (``lr``, ``wd``, ``rescale_grad``, ``t`` and their multi-tensor
plurals); constant schema floats (epsilon, momentum, beta1/2) are fine as
static keys and are not flagged.
"""
from __future__ import annotations

import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax

from .report import Finding, Report

try:                                    # jax >= 0.4.36
    from jax.extend import core as _core
except ImportError:                     # older: the classic namespace
    from jax import core as _core

__all__ = ["CollectiveEvent", "collect_collectives", "check_jaxpr",
           "check_fn", "check_collective_free", "check_symbol",
           "check_registry",
           "check_model_axis_replication", "check_capacity", "check_overlap",
           "check_embedding_grad", "check_decode_retrace",
           "is_decode_shaped", "check_trainer", "check_executor",
           "PER_STEP_ATTRS", "COLLECTIVE_PRIMS"]

# every collective primitive we track (axis_index is deliberately absent:
# it reads the axis env but moves no data and cannot desync)
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pgather",
    # what psum / all_gather trace to inside a check_vma shard_map
    "psum_invariant", "all_gather_invariant",
})

# attrs that change every optimizer step; static jit keys on these mean
# one fresh XLA compile per step (registry.dynamic_params is the fix).
# The canonical set lives next to the mechanism it polices.
from ..ops.registry import PER_STEP_PARAMS as PER_STEP_ATTRS  # noqa: E402

_JAXPR_TYPES = (_core.Jaxpr, _core.ClosedJaxpr)


def _as_jaxpr(j):
    """Normalize Jaxpr/ClosedJaxpr to the open Jaxpr."""
    return j.jaxpr if isinstance(j, _core.ClosedJaxpr) else j


def _source(eqn) -> str:
    """file:line of the python call that produced this eqn (best effort)."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is None:
            return ""
        return "%s:%d" % (frame.file_name, frame.start_line)
    except Exception:
        return ""


def _sub_jaxprs(eqn):
    """Yield (label, jaxpr) for every sub-jaxpr in an eqn's params —
    generic, so scan/cond/while/pjit/shard_map/remat/custom_vjp and any
    future higher-order primitive are all walked."""
    for key, val in sorted(eqn.params.items()):
        if isinstance(val, _JAXPR_TYPES):
            yield key, _as_jaxpr(val)
        elif isinstance(val, (tuple, list)):
            for i, item in enumerate(val):
                if isinstance(item, _JAXPR_TYPES):
                    yield "%s[%d]" % (key, i), _as_jaxpr(item)


def _axes_of(params) -> Tuple:
    """Normalized axis names of a collective eqn (strings only; positional
    axes are device-local and cannot mismatch)."""
    axes = params.get("axes", params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


class CollectiveEvent:
    """One collective eqn, in program order, with its jaxpr path."""

    __slots__ = ("prim", "axes", "path", "params", "source")

    def __init__(self, prim, axes, path, params, source):
        self.prim = prim
        self.axes = axes
        self.path = path
        self.params = params
        self.source = source

    def schedule_key(self):
        """The (kind, axes) pair two ranks must agree on to stay in step."""
        return (self.prim, self.axes)

    def __repr__(self):
        return "<Collective %s axes=%s at %s>" % (self.prim, self.axes,
                                                  self.path or "/")


def collect_collectives(jaxpr_like, path: str = "") -> List[CollectiveEvent]:
    """Ordered collective events of a (Closed)Jaxpr, descending into every
    nested jaxpr (scan/cond/while bodies, shard_map, pjit, remat...).
    Cond branches are labelled ``cond.branches[i]`` so callers can compare
    per-branch schedules."""
    events = []
    jaxpr = _as_jaxpr(jaxpr_like)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            events.append(CollectiveEvent(
                name, _axes_of(eqn.params), path, dict(eqn.params),
                _source(eqn)))
        for label, sub in _sub_jaxprs(eqn):
            sub_path = "%s/%s.%s" % (path, name, label) if path \
                else "%s.%s" % (name, label)
            events.extend(collect_collectives(sub, sub_path))
    return events


# ---------------------------------------------------------------------------
# jaxpr rule passes
# ---------------------------------------------------------------------------

def _walk_jaxprs(jaxpr_like, path: str = ""):
    """Yield (path, jaxpr) for the jaxpr and every nested jaxpr."""
    jaxpr = _as_jaxpr(jaxpr_like)
    yield path, jaxpr
    for eqn in jaxpr.eqns:
        for label, sub in _sub_jaxprs(eqn):
            sub_path = "%s/%s.%s" % (path, eqn.primitive.name, label) \
                if path else "%s.%s" % (eqn.primitive.name, label)
            yield from _walk_jaxprs(sub, sub_path)


def _mesh_axis_sizes(mesh) -> Optional[Dict[str, int]]:
    """Accept a Mesh, a {axis: size} mapping, or an iterable of names."""
    if mesh is None:
        return None
    shape = getattr(mesh, "shape", None)
    if shape is not None and hasattr(shape, "items"):
        return dict(shape.items())
    if hasattr(mesh, "items"):
        return dict(mesh.items())
    return {name: 0 for name in mesh}          # names only, sizes unknown


def _rule_axis_names(events, axis_sizes, rep: Report):
    for ev in events:
        unknown = [a for a in ev.axes if a not in axis_sizes]
        if unknown:
            rep.add(
                "GC101", "error",
                "%s over axis %s which the mesh (axes %s) does not define"
                % (ev.prim, unknown, sorted(axis_sizes)),
                location=ev.source or ev.path,
                fix_hint="use a mesh axis name, or add the axis to the "
                         "mesh this program runs under",
                extra={"path": ev.path, "axes": list(ev.axes)})


def _rule_cond_divergence(jaxpr_like, rep: Report, path: str = ""):
    jaxpr = _as_jaxpr(jaxpr_like)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "cond":
            branches = eqn.params.get("branches", ())
            schedules = [tuple(ev.schedule_key()
                               for ev in collect_collectives(b))
                         for b in branches]
            if len(set(schedules)) > 1:
                desc = ["branch%d=%s" % (i, [f"{p}@{','.join(a) or '-'}"
                                             for p, a in s])
                        for i, s in enumerate(schedules)]
                rep.add(
                    "GC102", "error",
                    "cond branches carry different collective schedules "
                    "(%s): ranks whose predicate diverges deadlock inside "
                    "the collective — the watchdog would only catch this "
                    "as a live hang" % "; ".join(desc),
                    location=_source(eqn) or (path or "/"),
                    fix_hint="hoist the collective out of the cond, or "
                             "make every branch issue the identical "
                             "collective sequence",
                    extra={"path": path,
                           "schedules": [[list(k) for k in s]
                                         for s in schedules]})
        elif name == "while":
            body = eqn.params.get("body_jaxpr")
            cond_j = eqn.params.get("cond_jaxpr")
            inner = []
            for part in (body, cond_j):
                if part is not None:
                    inner.extend(collect_collectives(part))
            if inner:
                rep.add(
                    "GC103", "warning",
                    "collective %s inside a while_loop: the trip count is "
                    "data-dependent, so ranks can disagree on iteration "
                    "count and desynchronize the schedule"
                    % sorted({ev.prim for ev in inner}),
                    location=_source(eqn) or (path or "/"),
                    fix_hint="prefer lax.scan with a static trip count, "
                             "or make the loop condition provably uniform "
                             "across ranks (e.g. psum the predicate)",
                    extra={"path": path})
        for label, sub in _sub_jaxprs(eqn):
            sub_path = "%s/%s.%s" % (path, name, label) if path \
                else "%s.%s" % (name, label)
            _rule_cond_divergence(sub, rep, sub_path)


def _rule_ppermute(events, axis_sizes, rep: Report):
    for ev in events:
        if ev.prim != "ppermute":
            continue
        perm = list(ev.params.get("perm") or ())
        srcs = [s for s, _ in perm]
        dsts = [d for _, d in perm]
        problems = []
        if len(set(srcs)) != len(srcs):
            problems.append("duplicate sources")
        if len(set(dsts)) != len(dsts):
            problems.append("duplicate destinations (two ranks send to "
                            "one; one transfer is silently dropped)")
        if axis_sizes:
            for axis in ev.axes:
                size = axis_sizes.get(axis) or 0
                if size and any(not (0 <= r < size) for r in srcs + dsts):
                    problems.append("rank outside axis %r of size %d"
                                    % (axis, size))
        if problems:
            rep.add(
                "GC104", "error",
                "ppermute perm %s is invalid: %s" % (perm,
                                                     "; ".join(problems)),
                location=ev.source or ev.path,
                fix_hint="a perm must be a partial bijection over "
                         "[0, axis_size)",
                extra={"path": ev.path, "perm": perm})


def _rule_axis_groups(events, axis_sizes, rep: Report):
    for ev in events:
        groups = ev.params.get("axis_index_groups")
        if not groups:
            continue
        sizes = {len(g) for g in groups}
        flat = [r for g in groups for r in g]
        problems = []
        if len(sizes) > 1:
            problems.append("groups of unequal size %s" % sorted(sizes))
        if len(set(flat)) != len(flat):
            problems.append("a rank appears in two groups")
        if axis_sizes:
            for axis in ev.axes:
                size = axis_sizes.get(axis) or 0
                if size and len(flat) != size:
                    problems.append(
                        "groups cover %d ranks but axis %r has %d — the "
                        "uncovered ranks never enter the collective"
                        % (len(flat), axis, size))
        if problems:
            rep.add(
                "GC105", "error",
                "%s axis_index_groups %s do not partition the axis: %s"
                % (ev.prim, groups, "; ".join(problems)),
                location=ev.source or ev.path,
                fix_hint="groups must be equal-sized, disjoint, and "
                         "cover every rank of the axis",
                extra={"path": ev.path})


def _rule_bf16_upcast(jaxpr_like, rep: Report):
    """bf16 -> f32 converts feeding dot/conv: the matmul silently runs at
    f32 MXU throughput (half the bf16 rate) — almost always an accidental
    upcast, since intentional f32 accumulation uses
    preferred_element_type, not an input convert."""
    import numpy as np
    for path, jaxpr in _walk_jaxprs(jaxpr_like):
        upcast_vars = {}
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "convert_element_type" \
                    and eqn.params.get("new_dtype") == np.dtype("float32") \
                    and str(eqn.invars[0].aval.dtype) == "bfloat16":
                upcast_vars[id(eqn.outvars[0])] = eqn
            elif name in ("dot_general", "conv_general_dilated"):
                for v in eqn.invars:
                    src = upcast_vars.get(id(v))
                    if src is not None:
                        rep.add(
                            "GC301", "warning",
                            "bf16 value upcast to f32 feeds %s directly: "
                            "the contraction runs at f32 rate instead of "
                            "bf16" % name,
                            location=_source(eqn) or path,
                            fix_hint="keep the operand bf16 and request "
                                     "f32 accumulation via "
                                     "preferred_element_type if needed",
                            extra={"path": path})
                        upcast_vars.pop(id(v), None)   # once per convert


def _rule_weak_types(closed, target: str, rep: Report):
    jaxpr = _as_jaxpr(closed)
    for i, v in enumerate(jaxpr.invars):
        aval = getattr(v, "aval", None)
        if aval is not None and getattr(aval, "weak_type", False):
            rep.add(
                "GC302", "warning",
                "input %d is a weak-typed %s scalar: a later call with a "
                "strongly-typed value (e.g. restored from checkpoint) "
                "misses the jit cache and recompiles the whole program"
                % (i, aval.dtype),
                location=target,
                fix_hint="pin the dtype at the call site: "
                         "jnp.asarray(x, jnp.float32)",
                extra={"arg_index": i})


def _rule_reshard_chain(jaxpr_like, rep: Report):
    for path, jaxpr in _walk_jaxprs(jaxpr_like):
        constrained = {}           # id(var) -> (sharding str, eqn)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "sharding_constraint":
                continue
            spec = str(eqn.params.get("sharding"))
            prev = constrained.get(id(eqn.invars[0]))
            if prev is not None and prev[0] != spec:
                rep.add(
                    "GC203", "warning",
                    "value is resharded %s -> %s back to back: each hop "
                    "is a collective copy on the hot path" % (prev[0],
                                                              spec),
                    location=_source(eqn) or path,
                    fix_hint="pick one sharding for the value, or move "
                             "the reshard off the per-step path",
                    extra={"path": path})
            for out in eqn.outvars:
                constrained[id(out)] = (spec, eqn)


def check_jaxpr(jaxpr_like, mesh=None, target: str = "") -> Report:
    """Run every jaxpr-level rule pass over a (Closed)Jaxpr.

    ``mesh``: a jax Mesh, a ``{axis: size}`` dict, or an iterable of axis
    names — enables the axis-existence and rank-range checks."""
    rep = Report("graphcheck", target)
    events = collect_collectives(jaxpr_like)
    axis_sizes = _mesh_axis_sizes(mesh)
    if axis_sizes is not None:
        _rule_axis_names(events, axis_sizes, rep)
    _rule_cond_divergence(jaxpr_like, rep)
    _rule_ppermute(events, axis_sizes, rep)
    _rule_axis_groups(events, axis_sizes, rep)
    _rule_bf16_upcast(jaxpr_like, rep)
    _rule_weak_types(jaxpr_like, target, rep)
    _rule_reshard_chain(jaxpr_like, rep)
    return rep


def check_fn(fn, *example_args, mesh=None, target: str = "",
             **example_kwargs) -> Report:
    """Trace ``fn`` (jitted or raw) with example args/structs and run the
    jaxpr rules.  Tracing only — nothing compiles, nothing executes."""
    closed = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
    return check_jaxpr(closed, mesh=mesh,
                       target=target or getattr(fn, "__name__", "fn"))


def check_collective_free(fn_or_jaxpr, *example_args,
                          target: str = "") -> Report:
    """GC106 over a program CONTRACTED to contain no collectives — the
    dist_async PS worker step (kvstore/worker.py): a worker's compute
    between pull and push must depend only on its own weights and batch,
    so a collective primitive anywhere in its trace is an error (a
    straggler peer would re-enter this rank's critical path, which is
    exactly what the async lane exists to prevent)."""
    if isinstance(fn_or_jaxpr, _JAXPR_TYPES):
        closed = fn_or_jaxpr
    else:
        closed = jax.make_jaxpr(fn_or_jaxpr)(*example_args)
        target = target or getattr(fn_or_jaxpr, "__name__", "fn")
    rep = Report("graphcheck", target)
    for ev in collect_collectives(closed):
        rep.add("GC106", "error",
                "collective `%s` over axes %s in a collective-free "
                "contract program" % (ev.prim, list(ev.axes)),
                location=ev.source or ev.path,
                fix_hint="move the collective out of the async worker "
                         "step, or run this program on the sync lane")
    return rep


# ---------------------------------------------------------------------------
# symbol / registry passes (recompile hazards)
# ---------------------------------------------------------------------------

def check_symbol(symbol, target: str = "") -> Report:
    """GC401/GC403 over a Symbol graph: per-step float attrs reaching ops
    as static jit keys, and attrs that cannot hash into a cache key."""
    from ..executor import GraphProgram
    rep = Report("graphcheck", target or (symbol.name or "symbol"))
    prog = GraphProgram(symbol)
    for node in prog.nodes:
        if node.is_var:
            continue
        try:
            attrs = node.parsed_attrs()
        except Exception:
            continue
        dyn = tuple(node.op.dynamic_params)
        for name, val in attrs.items():
            if name in PER_STEP_ATTRS and isinstance(val, float) \
                    and name not in dyn:
                rep.add(
                    "GC401", "warning",
                    "node %s (op %s) carries per-step attr %s=%r as a "
                    "STATIC jit key: every new value compiles a fresh "
                    "program" % (node.name, node.op.name, name, val),
                    location=node.name,
                    fix_hint="declare %r in the op's dynamic_params so "
                             "it rides as a traced input" % name,
                    extra={"op": node.op.name, "attr": name})
        try:
            hash(attrs.key())
        except TypeError as e:
            rep.add(
                "GC403", "error",
                "node %s (op %s) has attrs that cannot hash into a jit "
                "cache key: %s" % (node.name, node.op.name, e),
                location=node.name,
                fix_hint="attr values must be scalars/strings/tuples "
                         "(lists and dicts are converted; arbitrary "
                         "objects are not)",
                extra={"op": node.op.name})
    return rep


def check_registry(target: str = "ops.registry") -> Report:
    """GC402 over the live operator registry: any op whose schema declares
    a per-step param (lr/wd/rescale_grad/t/...) outside dynamic_params
    will recompile on every optimizer step."""
    from ..ops import registry as _registry
    rep = Report("graphcheck", target)
    seen = set()
    for name in _registry.list_ops():
        op = _registry.get_op(name)
        if id(op) in seen:            # aliases share the Operator
            continue
        seen.add(id(op))
        missing = [p for p in op.params
                   if p in PER_STEP_ATTRS and p not in op.dynamic_params]
        if missing:
            rep.add(
                "GC402", "warning",
                "op %s declares per-step params %s outside its "
                "dynamic_params %s" % (op.name, missing,
                                       list(op.dynamic_params)),
                location="ops/registry:%s" % op.name,
                fix_hint="add them to dynamic_params in the @register "
                         "call so schedules don't recompile the op",
                extra={"op": op.name, "missing": missing})
    return rep


# ---------------------------------------------------------------------------
# sharding/memory passes (need context a jaxpr no longer carries)
# ---------------------------------------------------------------------------

def _replicated_threshold_bytes() -> int:
    try:
        mb = float(os.environ.get("MXNET_TPU_PREFLIGHT_REPLICATED_MB", "8"))
    except ValueError:
        mb = 8.0
    return int(mb * (1 << 20))


def check_model_axis_replication(entries: Iterable[Tuple], mesh,
                      model_axes: Sequence[str] = (),
                      target: str = "") -> Report:
    """GC201: large arrays fully replicated while a model-parallel axis is
    active.  ``entries`` is ``(name, shape, dtype_itemsize, sharding)``;
    replication along pure-dp meshes is the normal design and not flagged.
    """
    rep = Report("graphcheck", target)
    axis_sizes = _mesh_axis_sizes(mesh) or {}
    active = [a for a in model_axes if axis_sizes.get(a, 1) > 1]
    if not active:
        return rep
    threshold = _replicated_threshold_bytes()
    for name, shape, itemsize, sharding in entries:
        n = 1
        for d in shape:
            n *= int(d)
        nbytes = n * int(itemsize)
        if nbytes < threshold:
            continue
        spec = getattr(sharding, "spec", None)
        fully_replicated = spec is None or all(s is None for s in spec)
        if fully_replicated:
            rep.add(
                "GC201", "warning",
                "%s (%.1f MB) is fully replicated although model-parallel "
                "axes %s are active: every device holds a full copy"
                % (name, nbytes / 1e6, active),
                location=name,
                fix_hint="shard it over a model axis (__shard__ attr / "
                         "PartitionSpec), or accept the HBM cost "
                         "explicitly (raise "
                         "MXNET_TPU_PREFLIGHT_REPLICATED_MB)",
                extra={"bytes": nbytes})
    return rep


def check_capacity(predicted_bytes, capacity_bytes=None, target: str = "",
                   detail: Optional[Dict] = None) -> Report:
    """GC501: pre-flight HBM capacity check — the memory-plane twin of
    the collective-schedule rules.  ``predicted_bytes`` comes from
    :func:`~mxnet_tpu.analysis.costmodel.predicted_peak_bytes` (state +
    batch, plus ``memory_analysis`` temps when a compile happened);
    ``capacity_bytes`` defaults to what the backend/env reports
    (``telemetry.memory.device_capacity_bytes``).  Silently passes when
    either side is unknown — a missing capacity must not block a dev
    box, the TPU allocator reports its own."""
    rep = Report("graphcheck", target)
    if capacity_bytes is None:
        from ..telemetry import memory as _memory
        capacity_bytes = _memory.device_capacity_bytes()
    if not predicted_bytes or not capacity_bytes:
        return rep
    if float(predicted_bytes) <= float(capacity_bytes):
        return rep
    extra = {"predicted_bytes": int(predicted_bytes),
             "capacity_bytes": int(capacity_bytes)}
    if detail:
        extra.update(detail)
    rep.add(
        "GC501", "error",
        "predicted peak HBM %.2f GB exceeds the %.2f GB device capacity "
        "(%.1fx): this program would die in the allocator as an opaque "
        "RESOURCE_EXHAUSTED mid-launch"
        % (predicted_bytes / 1e9, capacity_bytes / 1e9,
           predicted_bytes / capacity_bytes),
        location=target,
        fix_hint="cut the microbatch, enable gradient remat "
                 "(backward_mirror_policy), shard optimizer state "
                 "(shard_optimizer_state=True) or params (__shard__/tp), "
                 "and check buffer donation (GC202)",
        extra=extra)
    return rep


def _overlap_threshold_bytes() -> int:
    try:
        mb = float(os.environ.get("MXNET_TPU_GC304_MIN_MB", "1"))
    except ValueError:
        mb = 1.0
    return int(mb * (1 << 20))


def check_overlap(hlo_text: str, target: str = "",
                  min_bytes: Optional[int] = None) -> Report:
    """GC304: a compiled multi-device program that moves real collective
    payload with ZERO collective/compute overlap — nothing async with
    compute between ``-start``/``-done``, and every synchronous
    collective chained on the critical path between its producers and
    consumers (so no scheduler on any backend could hide the transfer).
    The oracle is the PR-6 static overlap instrument
    (:func:`~mxnet_tpu.analysis.costmodel.collective_compute_overlap`).

    Tiny programs (payload under ``MXNET_TPU_GC304_MIN_MB``, default
    1 MB) are not flagged: hiding microsecond transfers buys nothing and
    toy traces (tpulint's built-in entry points, the test fixtures)
    would drown the signal."""
    from . import costmodel
    rep = Report("graphcheck", target)
    ov = costmodel.collective_compute_overlap(hlo_text)
    threshold = _overlap_threshold_bytes() if min_bytes is None \
        else int(min_bytes)
    total_ops = ov["async_ops"] + ov["sync_ops"]
    if not total_ops or ov["collective_bytes"] < threshold:
        return rep
    if ov["overlapped_bytes"] > 0:
        return rep
    rep.add(
        "GC304", "warning",
        "all %d collectives (%.2f MB payload) run synchronously with "
        "zero compute overlap: every transfer is dead time on the "
        "critical path" % (total_ops, ov["collective_bytes"] / 1e6),
        location=target,
        fix_hint="double-buffer the schedule so each collective's "
                 "operand comes from the previous iteration and its "
                 "result is consumed in the next (parallel/ring.py and "
                 "parallel/pipeline.py are the worked examples), or "
                 "overlap per-tensor collectives with other tensors' "
                 "compute",
        extra={"collective_bytes": ov["collective_bytes"],
               "async_ops": ov["async_ops"], "sync_ops": ov["sync_ops"],
               "pipelined_ops": ov["pipelined_ops"]})
    return rep


def _zero_threshold_bytes() -> int:
    try:
        mb = float(os.environ.get("MXNET_TPU_GC305_MIN_MB", "8"))
    except ValueError:
        mb = 8.0
    return int(mb * (1 << 20))


def check_zero_update(dp_size: int, update_sharded: bool,
                      grad_payload_bytes, target: str = "",
                      min_bytes: Optional[int] = None) -> Report:
    """GC305: a dp-replicated parameter set paying ≥ threshold MB of
    pure-replica gradient all-reduce EVERY step while the ZeRO sharded
    weight update is off.  The reduce-scatter → shard-local update →
    weight all-gather form moves the same wire bytes but runs the
    optimizer at 1/dp FLOPs and state bytes per chip with the gather
    schedulable against other parameters' updates — leaving it off at
    real payloads is measurable money on the table ("Automatic
    Cross-Replica Sharding of Weight Update in Data-Parallel Training").
    Tiny payloads (under ``MXNET_TPU_GC305_MIN_MB``, default 8 MB) are
    not flagged: toy programs and the fixtures would drown the signal."""
    rep = Report("graphcheck", target)
    threshold = _zero_threshold_bytes() if min_bytes is None \
        else int(min_bytes)
    payload = int(grad_payload_bytes or 0)
    if dp_size <= 1 or update_sharded or payload < threshold:
        return rep
    rep.add(
        "GC305", "warning",
        "%.1f MB of gradients all-reduce fully replicated over dp=%d "
        "every step while the sharded weight update is off: each chip "
        "redundantly runs the full optimizer update and holds the full "
        "optimizer state" % (payload / 1e6, dp_size),
        location=target,
        fix_hint="enable the ZeRO update (ShardedTrainer(zero=True) or "
                 "MXNET_TPU_ZERO=1): grads reduce-scatter into dp "
                 "shards, the update runs at 1/dp FLOPs/bytes, new "
                 "weights all-gather back — identical numerics; or "
                 "raise MXNET_TPU_GC305_MIN_MB",
        extra={"grad_payload_bytes": payload, "dp_size": int(dp_size)})
    return rep


def _embedding_threshold_bytes() -> int:
    try:
        mb = float(os.environ.get("MXNET_TPU_GC306_MIN_MB", "8"))
    except ValueError:
        mb = 8.0
    return int(mb * (1 << 20))


def check_embedding_grad(hlo_text: str, table_bytes=None, target: str = "",
                         min_bytes: Optional[int] = None) -> Report:
    """GC306: the densified-embedding-gradient footgun.

    A program that routes a sharded-embedding lookup (the all-to-all
    signature of :mod:`mxnet_tpu.sparse.embedding`) should move gradient
    bytes proportional to *touched rows*; a single dense all-reduce /
    all-gather of full-table-sized payload in the same program means a
    table's gradient was materialized dense — usually a half-migrated
    model that still differentiates a replicated copy of a table, paying
    table-size wire bytes every step.

    ``table_bytes``: per-table GLOBAL byte sizes (defaults to the live
    :func:`~mxnet_tpu.sparse.embedding.live_tables` registry).  The
    flagging threshold is ``max(MXNET_TPU_GC306_MIN_MB, half the
    smallest table)`` so toy MLP grads in the same program never trip
    it.  Payload conventions match ``parallel.audit``: sync ops report
    result bytes, async ``-start`` their operand bytes."""
    from . import costmodel
    rep = Report("graphcheck", target)
    instrs = list(costmodel.iter_instructions(hlo_text))
    if not any(i.opcode.split("-start")[0] == "all-to-all"
               for i in instrs):
        return rep          # no routed lookup in this program
    if table_bytes is None:
        try:
            from ..sparse.embedding import live_tables
            table_bytes = [b for _n, b in live_tables()]
        except Exception:
            table_bytes = []
    table_bytes = [int(b) for b in (table_bytes or []) if b]
    floor = _embedding_threshold_bytes() if min_bytes is None \
        else int(min_bytes)
    threshold = max(floor, min(table_bytes) // 2) if table_bytes else floor
    for ins in instrs:
        op = ins.opcode
        base = op[:-len("-start")] if op.endswith("-start") else op
        if base not in ("all-reduce", "all-gather") or \
                op.endswith("-done"):
            continue
        payload = ins.operand_bytes if op.endswith("-start") \
            else ins.result_bytes
        if payload < threshold:
            continue
        rep.add(
            "GC306", "warning",
            "%s %r moves %.1f MB in ONE dense collective while this "
            "program also routes a sharded-embedding lookup: an "
            "embedding gradient was densified, so wire bytes scale "
            "with table size (%s MB tables live) instead of touched "
            "rows" % (base, ins.name, payload / 1e6,
                      ",".join("%.0f" % (b / 1e6) for b in table_bytes)
                      or "?"),
            location=target,
            fix_hint="differentiate with respect to the looked-up ROWS "
                     "and feed (ids, grad_rows) to ShardedEmbedding."
                     "apply_sgd/apply_adam (the touched-rows lazy "
                     "update); shard the table with __shard__/P(axis) "
                     "instead of replicating it; or raise "
                     "MXNET_TPU_GC306_MIN_MB",
            extra={"payload_bytes": int(payload), "instruction": ins.name,
                   "table_bytes": table_bytes})
    return rep


_CACHE_WRITE_PRIMS = frozenset({"scatter", "dynamic_update_slice",
                                "dynamic-update-slice", "concatenate"})


def is_decode_shaped(jaxpr_like) -> bool:
    """Heuristic decode signature: the program writes in place into a
    cache-like buffer (scatter / dynamic_update_slice, or a Pallas kernel
    that aliases an operand into its result, as ``kv_write`` does) AND
    contracts a query against an operand at least an order of magnitude
    larger (the single-query-vs-cached-K/V shape of decode attention)."""
    has_write = False
    has_sq_attn = False
    for _path, jaxpr in _walk_jaxprs(jaxpr_like):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _CACHE_WRITE_PRIMS or (
                    name == "pallas_call"
                    and eqn.params.get("input_output_aliases")):
                has_write = True
            elif name == "dot_general" and len(eqn.invars) >= 2:
                sizes = []
                for v in eqn.invars[:2]:
                    aval = getattr(v, "aval", None)
                    n = 1
                    for d in getattr(aval, "shape", ()) or ():
                        n *= int(d)
                    sizes.append(n)
                if min(sizes) and max(sizes) >= 16 * min(sizes):
                    has_sq_attn = True
    return has_write and has_sq_attn


def check_decode_retrace(step_fn, args_a, args_b,
                         target: str = "") -> Report:
    """GC307: the recompile-per-token trap.

    ``args_a`` / ``args_b`` are two example argument tuples for the SAME
    decode step at different generation states (another token position,
    another sequence length, another batch membership).  A correctly
    built step (fixed cache shapes, position/length as traced DATA)
    traces to the identical jaxpr for both; a step that bakes either
    into the trace — python-int positions as static args, a cache that
    grows by concatenation, per-length padding — produces different
    avals or different jaxprs, which at serving time means one fresh XLA
    compile per generated token.  Only decode-shaped programs
    (:func:`is_decode_shaped`) are judged; anything else passes
    silently so the rule can sit on generic entry points."""
    rep = Report("graphcheck", target or "decode")
    try:
        closed_a = jax.make_jaxpr(step_fn)(*args_a)
    except TypeError as e:
        # the step coerces a traced value to a host int (int(pos),
        # shape arithmetic from the position, ...) — under jit that
        # value is a STATIC cache key and every new position recompiles
        rep.add(
            "GC307", "warning",
            "decode step cannot trace with its generation state held "
            "abstract (%s): a step/position/length is consumed as a "
            "host value, so under jit it becomes a static cache key "
            "and every generated token compiles a fresh program"
            % (str(e).splitlines()[0][:160],),
            location=target,
            fix_hint="pass step/position/length as traced int32 arrays "
                     "and index with lax.dynamic_update_slice / "
                     "gather, never int(pos) or pos-derived shapes")
        return rep
    avals_a = [str(v.aval) for v in closed_a.jaxpr.invars]
    if not is_decode_shaped(closed_a):
        return rep
    closed_b = jax.make_jaxpr(step_fn)(*args_b)
    avals_b = [str(v.aval) for v in closed_b.jaxpr.invars]
    if avals_a != avals_b:
        changed = [i for i, (a, b) in enumerate(zip(avals_a, avals_b))
                   if a != b][:4]
        rep.add(
            "GC307", "warning",
            "decode step input SHAPES change with generation state "
            "(args %s: %s -> %s): every step/sequence-length change "
            "recompiles the program — one fresh XLA compile per "
            "generated token"
            % (changed,
               [avals_a[i] for i in changed],
               [avals_b[i] for i in changed]),
            location=target,
            fix_hint="hold K/V in a fixed page pool indexed by a page "
                     "table (serving/decode.PagedKVCache layout) and "
                     "mask by seq_len instead of slicing to it",
            extra={"changed_args": changed})
        return rep
    if str(closed_a) != str(closed_b):
        rep.add(
            "GC307", "warning",
            "decode step traces DIFFERENTLY at two generation states "
            "with identical input shapes: a step/position/length is "
            "baked into the trace as a constant, so every token change "
            "misses the jit cache and recompiles",
            location=target,
            fix_hint="pass the changing value as a traced int32 array "
                     "argument (it must appear in the jaxpr as an input, "
                     "not a literal)")
    return rep


def check_donation(donated: bool, what: str, target: str = "") -> Report:
    """GC202: the training step's state buffers (params/momenta/guard)
    must be donated or the update holds old+new copies live — 2x peak."""
    rep = Report("graphcheck", target)
    if not donated:
        rep.add(
            "GC202", "warning",
            "%s run without buffer donation: the update keeps the old and "
            "new state live simultaneously (2x peak HBM)" % what,
            location=target,
            fix_hint="pass donate_argnums covering params and optimizer "
                     "state to jax.jit")
    return rep


# ---------------------------------------------------------------------------
# whole-program entry points
# ---------------------------------------------------------------------------

def check_trainer(trainer, params, mom, aux, inputs, keys=None,
                  guard=None) -> Tuple[Report, object]:
    """Full pre-flight over a ShardedTrainer's step program.

    Traces the exact raw step function the trainer jits (same remat
    policy, same guard automaton) and runs every pass.  Returns
    ``(report, closed_jaxpr)`` so callers can persist the jaxpr artifact.
    """
    keys = keys if keys is not None else trainer._keys()
    guard = guard if guard is not None else trainer._guard_arrays()
    step_fn = trainer._make_step_fn()
    closed = jax.make_jaxpr(step_fn)(params, mom, aux, inputs, keys, guard)
    target = "ShardedTrainer(%s)" % (trainer.symbol.name or "symbol")
    rep = check_jaxpr(closed, mesh=trainer.spec.mesh, target=target)
    rep.extend(check_symbol(trainer.symbol, target=target))
    rep.extend(check_registry())
    shardings = trainer._param_shardings()
    entries = [(n, trainer._param_shapes.get(n, ()), 4, s)
               for n, s in zip(trainer.param_names, shardings)]
    model_axes = [a for a in (trainer.tp_axis,) if a]
    rep.extend(check_model_axis_replication(entries, trainer.spec.mesh, model_axes,
                                 target=target))
    rep.extend(check_donation(getattr(trainer, "_step_donated", True),
                              "ShardedTrainer jitted step", target=target))
    # GC305: pure-replica grad all-reduce while the ZeRO update is off
    grad_payload = 0
    for n in trainer.param_names:
        count = 1
        for d in trainer._param_shapes.get(n, ()):
            count *= int(d)
        grad_payload += 4 * count
    rep.extend(check_zero_update(
        trainer.spec.dp_size,
        getattr(trainer, "shard_weight_update", False),
        grad_payload, target=target))
    # GC501: predicted peak HBM (state + batch; the costmodel's donated
    # vs undonated accounting) against the device capacity, BEFORE any
    # buffer is allocated
    from . import costmodel

    def _leaf_bytes(tree):
        import numpy as np
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                continue
            n = 1
            for d in shape:
                n *= int(d)
            total += n * np.dtype(dtype).itemsize
        return total

    state_bytes = _leaf_bytes((params, mom, aux))
    batch_bytes = _leaf_bytes(inputs)
    predicted = costmodel.predicted_peak_bytes(
        state_bytes, batch_bytes,
        donated=getattr(trainer, "_step_donated", True))
    rep.extend(check_capacity(
        predicted, target=target,
        detail={"state_bytes": state_bytes, "batch_bytes": batch_bytes,
                "donated": getattr(trainer, "_step_donated", True)}))
    rep.target = target
    return rep, closed


def check_executor(executor, train: bool = True) -> Tuple[Report, object]:
    """Pre-flight over a bound Executor's fused forward+backward program
    (the Module path).  Traces with the executor's own buffers as shape
    structs; returns ``(report, closed_jaxpr)``."""
    prog = executor._prog
    args = tuple(a._handle for a in executor.arg_arrays)
    aux = tuple(a._handle for a in executor.aux_arrays)
    keys = executor._keys()
    mask = tuple(executor.grad_req.get(n, "null") != "null"
                 for n in prog.arg_names)
    target = "Executor(%s)" % (executor._symbol.name or "symbol")
    fwd = prog._jit_forward(bool(train))
    if any(mask):
        outs, _ = jax.eval_shape(fwd, args, aux, keys)
        cots = tuple(jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs)
        fb = prog._jit_fwd_bwd(bool(train), mask)
        closed = jax.make_jaxpr(fb)(args, aux, keys, cots)
    else:
        closed = jax.make_jaxpr(fwd)(args, aux, keys)
    rep = check_jaxpr(closed, target=target)
    rep.extend(check_symbol(executor._symbol, target=target))
    rep.extend(check_registry())
    rep.target = target
    return rep, closed
