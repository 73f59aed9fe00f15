"""Analytic cost model over optimized HLO text.

The static half of the performance attribution plane (the dynamic half —
telemetry histograms and the span split — lives in
:mod:`mxnet_tpu.telemetry.perf`).  Given the optimized HLO of a compiled
program this module computes, WITHOUT executing anything:

* **analytic FLOPs** — dot/convolution contractions from their shapes
  (2·|out|·K), elementwise arithmetic at one flop per output element,
  reduces at one flop per input element; transcendentals counted in
  their own bucket the way ``HloCostAnalysis`` does.  Validated against
  ``Compiled.cost_analysis()`` within 5% on seeded programs
  (tests/test_perf_attribution.py).
* **instruction bytes by op class × dtype** — every instruction's
  result bytes grouped by ``(opcode, dtype)``: the accounting PERF.md
  r4/r5 derived by hand ("+4.9 GB f32 add around every BatchNorm") now
  computed mechanically, with the f32-vs-bf16 split and top-N
  contributors a perf round starts from.
* **collective payloads** — via :func:`parallel.audit
  .collective_accounting` (one parser, already CI-validated to 1.00× of
  the analytic ring model at dp8).
* **collective/compute overlap** — walks each computation's instruction
  schedule and reports what fraction of collective payload bytes is
  issued async (``-start``/``-done``) with real compute between start
  and done: the standing instrument behind ROADMAP item 2's "spans
  prove the overlap" criterion.  Synchronous collectives are by
  construction 0% overlapped.
* **roofline** — peak-normalized compute/HBM/collective times and which
  roof binds, against per-chip peaks (see :func:`chip_peaks`).

"A Learned Performance Model for TPUs" (PAPERS.md) starts from exactly
these analytic features; TVM automates the same accounting with a
measurement harness.  This module is the feature extractor both
directions share.
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["HloInstr", "iter_instructions", "analytic_flops",
           "instruction_bytes", "bytes_by_dtype", "top_contributors",
           "collective_compute_overlap", "CHIP_PEAKS", "chip_peaks",
           "roofline",
           "entry_io_bytes", "memory_breakdown", "predicted_peak_bytes"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(pred|bf16|f8e4m3fn|f8e5m2|[sufc]\d+)\[([\d,]*)\]")
_OPERAND_REF_RE = re.compile(r"%([\w.\-]+)")

# instruction line: `[ROOT ]%name = TYPE opcode(operands...), attrs...`
# (same shape as parallel/audit.py's collective matcher, kept permissive:
# TYPE may be a tuple of shapes, opcode is the lowercase HLO op name)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?.+?\)?)\s+([a-z][\w\-]*)\(")

# one flop per output element (XLA HloCostAnalysis HandleElementwiseOp)
_ELEMENTWISE = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "negate", "abs", "sign", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "compare", "select", "clamp", "and", "or",
    "xor", "not", "shift-left", "shift-right-arithmetic",
    "shift-right-logical", "remainder", "atan2", "convert", "is-finite",
})

# counted in HloCostAnalysis's transcendental bucket, not flops
_TRANSCENDENTAL = frozenset({
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "sqrt", "rsqrt", "cbrt", "power", "logistic", "sine",
    "cosine", "tan", "erf",
})

_COLLECTIVE_BASES = ("all-reduce", "reduce-scatter", "all-gather",
                     "all-to-all", "collective-permute")

# opcodes that do real work between an async collective's start and done
# (data movement like copy/bitcast/tuple does not hide latency)
_COMPUTE_OPS = frozenset(
    {"dot", "convolution", "fusion", "custom-call", "reduce",
     "reduce-window", "scatter", "gather", "sort", "while", "call",
     "conditional", "cholesky", "triangular-solve"}
    | _ELEMENTWISE | _TRANSCENDENTAL)


def _elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _shape_tokens(expr: str) -> List[Tuple[str, int]]:
    """Every (dtype, element_count) in a type expression (handles
    tuples)."""
    return [(dt, _elems(dims)) for dt, dims in _SHAPE_RE.findall(expr)]


def _type_bytes(expr: str) -> int:
    return sum(n * _DTYPE_BYTES.get(dt, 4) for dt, n in _shape_tokens(expr))


def _balanced_operands(line: str, open_idx: int) -> Tuple[str, str]:
    """Split an instruction line at the opcode's argument list: returns
    (operands_text, trailing_attrs_text).  ``open_idx`` is the index of
    the opening paren."""
    depth = 0
    for i in range(open_idx, len(line)):
        ch = line[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return line[open_idx + 1:i], line[i + 1:]
    return line[open_idx + 1:], ""


class HloInstr:
    """One parsed HLO instruction."""

    __slots__ = ("name", "opcode", "result_type", "result_dtype",
                 "result_bytes", "operands", "operand_shapes",
                 "operand_bytes", "attrs", "computation")

    def __init__(self, name, opcode, result_type, operands, attrs,
                 computation, types=None):
        self.name = name
        self.opcode = opcode
        self.result_type = result_type
        toks = _SHAPE_RE.findall(result_type)
        self.result_dtype = toks[0][0] if toks else "?"
        self.result_bytes = _type_bytes(result_type)
        self.operands = operands
        # the installed XLA prints operands as bare `%name` references;
        # their types are those of the instructions that defined them
        # (``types``: name -> result type, filled by iter_instructions)
        typed = operands
        if types and not _SHAPE_RE.search(operands):
            typed = " ".join(types.get(ref, "")
                             for ref in _OPERAND_REF_RE.findall(operands))
        # [(dtype, [dims...]), ...] in operand order
        self.operand_shapes = [
            (dt, [int(d) for d in dims.split(",") if d])
            for dt, dims in _SHAPE_RE.findall(typed)]
        self.operand_bytes = _type_bytes(typed)
        self.attrs = attrs
        self.computation = computation

    def __repr__(self):
        return "<HloInstr %s = %s (%s)>" % (self.name, self.opcode,
                                            self.result_type)


def iter_instructions(hlo_text: str) -> Iterator[HloInstr]:
    """Parse every instruction line of an HLO module dump, tracking which
    computation (ENTRY, fused_computation, region, ...) each belongs to.
    Fusion bodies are listed as their own computations, so their inner
    dot/convolution instructions are visible — which is exactly what the
    per-op-class accounting wants."""
    computation = ""
    types: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ("->" in stripped
                                       or stripped.startswith("ENTRY")):
            head = stripped.split("(", 1)[0].strip()
            computation = head.lstrip("%") or "?"
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rtype, opcode = m.groups()
        operands, attrs = _balanced_operands(line, m.end() - 1)
        types[name] = rtype
        yield HloInstr(name, opcode, rtype, operands, attrs, computation,
                       types)


# ---------------------------------------------------------------------------
# analytic FLOPs
# ---------------------------------------------------------------------------

def _dot_flops(ins: HloInstr) -> int:
    out_elems = sum(n for _, n in _shape_tokens(ins.result_type))
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.attrs)
    if not m or not ins.operand_shapes:
        return 2 * out_elems          # degenerate: no contraction info
    lhs_dims = ins.operand_shapes[0][1]
    k = 1
    for idx in (int(d) for d in m.group(1).split(",") if d):
        if idx < len(lhs_dims):
            k *= lhs_dims[idx]
    return 2 * out_elems * max(1, k)


def _parse_window(attrs: str, n: int):
    """(size, stride, pad_lo, pad_hi, lhs_dilate, rhs_dilate) per spatial
    dim from a ``window={...}`` spec; None when unparsable."""
    m = re.search(r"window=\{([^}]*)\}", attrs)
    fields = {}
    if m:
        for part in m.group(1).split():
            if "=" in part:
                k, v = part.split("=", 1)
                fields[k] = v

    def ints(key, default):
        v = fields.get(key)
        if v is None:
            return [default] * n
        return [int(t) for t in v.split("x") if t]

    size = ints("size", 1)
    if len(size) != n:
        return None
    pad = fields.get("pad")
    if pad is None:
        plo, phi = [0] * n, [0] * n
    else:
        plo, phi = [], []
        for t in pad.split("x"):
            lo, hi = t.split("_")
            plo.append(int(lo))
            phi.append(int(hi))
        if len(plo) != n:
            return None
    return (size, ints("stride", 1), plo, phi,
            ints("lhs_dilate", 1), ints("rhs_dilate", 1))


def _dim_valid_taps(I, k, plo, phi, s, ld, rd):
    """Count (output position, kernel tap) pairs that land on a real
    input element along one spatial dim — in bounds AND not a zero hole
    interleaved by lhs dilation.  This is the per-dim factor XLA's
    HloCostAnalysis multiplies into conv flops, so padded borders and
    strided-conv gradients (lhs_dilate) cost what they actually cost."""
    Id = (I - 1) * ld + 1 if I > 0 else 0
    ke = (k - 1) * rd + 1
    O = (Id + plo + phi - ke) // s + 1
    valid = 0
    for o in range(max(0, O)):
        start = o * s - plo
        for j in range(k):
            pos = start + j * rd
            if 0 <= pos < Id and pos % ld == 0:
                valid += 1
    return valid


def _conv_flops(ins: HloInstr) -> int:
    """2 · batch · out_features · kernel_in_features · valid spatial
    taps, matching ``HloCostAnalysis::HandleConvolution`` (grouping folds
    in through the kernel's input-feature extent)."""
    out_toks = _SHAPE_RE.findall(ins.result_type)
    out_elems = sum(n for _, n in _shape_tokens(ins.result_type))
    m = re.search(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)", ins.attrs)
    if not m or len(ins.operand_shapes) < 2 or not out_toks:
        return 2 * out_elems
    lhs_spec, ker_spec, out_spec = m.groups()
    lhs = ins.operand_shapes[0][1]
    ker = ins.operand_shapes[1][1]
    out_dims = [int(d) for d in out_toks[0][1].split(",") if d]
    if len(lhs) != len(lhs_spec) or len(ker) != len(ker_spec) \
            or len(out_dims) != len(out_spec):
        return 2 * out_elems
    digits = [c for c in lhs_spec if c.isdigit()]
    win = _parse_window(ins.attrs, len(digits))
    if win is None:
        return 2 * out_elems
    size, stride, plo, phi, ld, rd = win
    batch = out_dims[out_spec.index("b")] if "b" in out_spec else 1
    out_f = out_dims[out_spec.index("f")] if "f" in out_spec else 1
    ker_i = ker[ker_spec.index("i")] if "i" in ker_spec else 1
    valid = 1
    for si, c in enumerate(digits):
        valid *= _dim_valid_taps(lhs[lhs_spec.index(c)], size[si],
                                 plo[si], phi[si], stride[si], ld[si],
                                 rd[si])
    return 2 * batch * out_f * ker_i * valid


def analytic_flops(hlo_text: str) -> Dict[str, float]:
    """``{"flops": total, "transcendentals": total, "by_op": {...}}`` —
    the pre-execution FLOP model over the optimized module.  Note: a
    while-loop body is counted ONCE (trip counts are dynamic); the
    repo's hot programs are scan-free unrolled steps where this is
    exact."""
    flops = 0
    trans = 0
    by_op: Dict[str, float] = {}
    for ins in iter_instructions(hlo_text):
        op = ins.opcode
        base = op[:-len("-start")] if op.endswith("-start") else op
        if op == "dot":
            f = _dot_flops(ins)
        elif op == "convolution":
            f = _conv_flops(ins)
        elif base in ("all-reduce", "reduce-scatter"):
            # HloCostAnalysis charges the reduction one flop per output
            # element; '-done' carries no work of its own
            f = sum(n for _, n in _shape_tokens(ins.result_type))
        elif op in _ELEMENTWISE:
            f = sum(n for _, n in _shape_tokens(ins.result_type))
        elif op in ("reduce", "reduce-window"):
            # one flop per reduced input element (first operand)
            f = _prod(ins.operand_shapes[0][1]) if ins.operand_shapes \
                else sum(n for _, n in _shape_tokens(ins.result_type))
        elif op in _TRANSCENDENTAL:
            trans += sum(n for _, n in _shape_tokens(ins.result_type))
            continue
        else:
            continue
        flops += f
        by_op[op] = by_op.get(op, 0) + f
    return {"flops": float(flops), "transcendentals": float(trans),
            "by_op": {k: float(v) for k, v in
                      sorted(by_op.items(), key=lambda kv: -kv[1])}}


def _prod(dims):
    n = 1
    for d in dims:
        n *= d
    return n


# ---------------------------------------------------------------------------
# instruction bytes by op class × dtype
# ---------------------------------------------------------------------------

_SKIP_BYTE_OPS = frozenset({
    # zero-cost views / bookkeeping: counting them as byte traffic would
    # double every value once per alias
    "bitcast", "tuple", "get-tuple-element", "parameter", "constant",
    "after-all", "partition-id", "replica-id", "opt-barrier",
})


def instruction_bytes(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Result bytes per op class, split by dtype:
    ``{opcode: {dtype: bytes}}``.  This is "instruction bytes" in the
    PERF.md r4/r5 sense — a per-op-class traffic proxy over the whole
    module (fusion bodies included), NOT the deduplicated HBM footprint
    (use ``Compiled.cost_analysis()['bytes accessed']`` for the roofline
    number)."""
    out: Dict[str, Dict[str, int]] = {}
    for ins in iter_instructions(hlo_text):
        if ins.opcode in _SKIP_BYTE_OPS or not ins.result_bytes:
            continue
        slot = out.setdefault(ins.opcode, {})
        slot[ins.result_dtype] = slot.get(ins.result_dtype, 0) \
            + ins.result_bytes
    return out


def bytes_by_dtype(per_class: Dict[str, Dict[str, int]]) -> Dict[str, int]:
    """Collapse the per-class table to the f32-vs-bf16 (etc.) split."""
    out: Dict[str, int] = {}
    for dts in per_class.values():
        for dt, b in dts.items():
            out[dt] = out.get(dt, 0) + b
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def top_contributors(per_class: Dict[str, Dict[str, int]],
                     n: int = 10) -> List[Dict]:
    """The top-N ``(op class, dtype)`` byte contributors, largest
    first — the "name the top-3" table a perf round opens with."""
    flat = [{"op": op, "dtype": dt, "bytes": b}
            for op, dts in per_class.items() for dt, b in dts.items()]
    flat.sort(key=lambda e: -e["bytes"])
    return flat[:n]


# ---------------------------------------------------------------------------
# memory: entry-signature prediction + compiled breakdown
# ---------------------------------------------------------------------------

_ENTRY_RE = re.compile(r"^\s*ENTRY\s+%?[\w.\-]+\s*")


def entry_io_bytes(hlo_text: str) -> Dict[str, int]:
    """Predicted argument/output bytes of a module from its ENTRY
    signature alone: ``{"argument_bytes", "output_bytes"}``.

    This is the costmodel side of the memory reconciliation — the
    numbers ``Compiled.memory_analysis()`` reports as
    ``argument_size_in_bytes``/``output_size_in_bytes`` re-derived from
    the HLO text (they differ only by layout padding), so the
    attribution report can cross-check the parser against XLA the same
    way the FLOP model is cross-checked against ``cost_analysis()``."""
    for line in hlo_text.splitlines():
        if not _ENTRY_RE.match(line):
            continue
        open_idx = line.find("(")
        if open_idx < 0:
            continue
        params, rest = _balanced_operands(line, open_idx)
        out_type = rest.split("->", 1)[1] if "->" in rest else ""
        return {"argument_bytes": _type_bytes(params),
                "output_bytes": _type_bytes(out_type.split("{")[0])}
    return {"argument_bytes": 0, "output_bytes": 0}


def memory_breakdown(compiled_or_stats) -> Dict[str, int]:
    """Normalize ``Compiled.memory_analysis()`` (or an already-fetched
    ``CompiledMemoryStats``) into plain ints:
    ``{argument,output,temp,alias,generated_code,peak}_bytes``.

    ``peak_bytes`` follows XLA's accounting: arguments + outputs +
    temps − aliased bytes (a donated train step aliases params/momenta
    in-place, so its peak is ~1× state, not 2×).  Empty dict when the
    executable cannot report (some deserialized AOT artifacts)."""
    stats = compiled_or_stats
    if hasattr(stats, "memory_analysis"):
        try:
            stats = stats.memory_analysis()
        except Exception:
            return {}
    if stats is None:
        return {}
    def grab(field):
        try:
            return int(getattr(stats, field))
        except (AttributeError, TypeError, ValueError):
            return 0
    out = {
        "argument_bytes": grab("argument_size_in_bytes"),
        "output_bytes": grab("output_size_in_bytes"),
        "temp_bytes": grab("temp_size_in_bytes"),
        "alias_bytes": grab("alias_size_in_bytes"),
        "generated_code_bytes": grab("generated_code_size_in_bytes"),
    }
    if not any(out.values()):
        return {}
    out["peak_bytes"] = max(0, out["argument_bytes"] + out["output_bytes"]
                            + out["temp_bytes"] - out["alias_bytes"])
    return out


def predicted_peak_bytes(state_bytes: float, batch_bytes: float = 0.0,
                         temp_bytes: float = 0.0,
                         donated: bool = True) -> int:
    """Pre-compile peak-HBM prediction for a training-step-shaped
    program (the GC501 input): persistent state (params + optimizer +
    aux) once when the update donates its buffers, TWICE when it does
    not (old and new live simultaneously — the GC202 hazard), plus the
    batch and whatever temp estimate the caller has (0 before a
    compile; ``memory_breakdown()['temp_bytes']`` after one)."""
    factor = 1.0 if donated else 2.0
    return int(factor * float(state_bytes) + float(batch_bytes)
               + float(temp_bytes))


# ---------------------------------------------------------------------------
# collective/compute overlap
# ---------------------------------------------------------------------------

# ops with enough arithmetic to hide a transfer behind (MXU-class work or
# nested control flow that contains it).  Deliberately excludes fusions
# and elementwise: a bookkeeping scatter next to a boundary ppermute must
# not read as "the hop is hidden" (exactly the pre-fix GPipe schedule).
_HEAVY_COMPUTE_OPS = frozenset({
    "dot", "convolution", "custom-call", "while", "call", "conditional",
    "cholesky", "triangular-solve",
})



def _pipelined_sync_collectives(instrs: List[HloInstr]) -> Dict[str, bool]:
    """For each SYNC collective in one computation: is there at least one
    heavy compute instruction that is neither an ancestor nor a
    descendant of it?  If so the transfer has real work to hide behind —
    an async backend (TPU converts these to ``-start``/``-done`` pairs)
    overlaps it; a schedule where every collective sits on the critical
    path between its producers and consumers cannot be overlapped by ANY
    scheduler.  Returns ``{instr_name: pipelined}``."""
    by_name = {ins.name: i for i, ins in enumerate(instrs)}
    deps: List[List[int]] = []
    users: List[List[int]] = [[] for _ in instrs]
    for i, ins in enumerate(instrs):
        dd = []
        for ref in _OPERAND_REF_RE.findall(ins.operands):
            j = by_name.get(ref)
            if j is not None and j != i:
                dd.append(j)
                users[j].append(i)
        deps.append(dd)
    heavy = [i for i, ins in enumerate(instrs)
             if ins.opcode in _HEAVY_COMPUTE_OPS]
    out = {}
    for c, ins in enumerate(instrs):
        if ins.opcode not in _COLLECTIVE_BASES:
            continue
        related = {c}
        # reverse BFS over operands (ancestors) + forward over users
        for seed, edges in ((c, deps), (c, users)):
            todo = [seed]
            seen = {seed}
            while todo:
                cur = todo.pop()
                for nxt in edges[cur]:
                    if nxt not in seen:
                        seen.add(nxt)
                        todo.append(nxt)
            related |= seen
        out[ins.name] = any(h not in related for h in heavy)
    return out


def collective_compute_overlap(hlo_text: str) -> Dict:
    """Static overlap instrument: of the module's collective payload
    bytes, how much has real compute to hide behind?

    Two classifications feed ``overlapped_bytes``:

    * **async** — an explicit ``-start`` whose matching ``-done`` has at
      least one compute instruction scheduled in between (XLA already
      realized the overlap; TPU HLO).
    * **pipelined** — a synchronous collective whose computation holds
      at least one heavy compute op (dot/conv-class) that is neither its
      ancestor nor its descendant: the schedule is double-buffered, so a
      backend with async collectives hides the transfer behind that
      compute.  This is how overlap is proven on backends (XLA:CPU — the
      dryrun audit) that never emit ``-start``/``-done``: a collective
      on the critical path between its producers and consumers (the
      pre-fix GPipe boundary hop) cannot be overlapped by ANY scheduler
      and counts 0.

    Returns ``{"collective_bytes", "overlapped_bytes", "overlap_pct",
    "async_ops", "sync_ops", "pipelined_ops", "by_kind"}``;
    ``overlap_pct`` is None when the program has no collectives."""
    total = 0
    overlapped = 0
    async_ops = 0
    sync_ops = 0
    pipelined_ops = 0
    by_kind: Dict[str, Dict[str, int]] = {}
    # per-computation schedule walk
    open_starts: Dict[Tuple[str, str], dict] = {}
    sync_payload: Dict[Tuple[str, str], Tuple[str, int]] = {}
    per_comp: Dict[str, List[HloInstr]] = {}

    def kind_slot(kind):
        return by_kind.setdefault(kind, {"bytes": 0, "overlapped": 0,
                                         "async": 0, "sync": 0,
                                         "pipelined": 0})

    for ins in iter_instructions(hlo_text):
        per_comp.setdefault(ins.computation, []).append(ins)
        op = ins.opcode
        base = op
        is_start = op.endswith("-start")
        is_done = op.endswith("-done")
        if is_start:
            base = op[:-len("-start")]
        elif is_done:
            base = op[:-len("-done")]
        if base in _COLLECTIVE_BASES:
            if is_done:
                # match by operand reference to the -start's name
                for (comp, sname), rec in list(open_starts.items()):
                    if comp == ins.computation and \
                            "%" + sname in ins.operands:
                        if rec["compute_between"]:
                            overlapped += rec["bytes"]
                            kind_slot(base)["overlapped"] += rec["bytes"]
                        del open_starts[(comp, sname)]
                        break
                continue
            payload = ins.operand_bytes
            total += payload
            slot = kind_slot(base)
            slot["bytes"] += payload
            if is_start:
                async_ops += 1
                slot["async"] += 1
                open_starts[(ins.computation, ins.name)] = {
                    "bytes": payload, "compute_between": False}
            else:
                sync_ops += 1
                slot["sync"] += 1
                sync_payload[(ins.computation, ins.name)] = (base, payload)
            continue
        if op in _COMPUTE_OPS:
            for rec in open_starts.values():
                rec["compute_between"] = True
    # second pass: schedulable overlap for the sync collectives
    if sync_payload:
        pipelined_by_comp = {
            comp: _pipelined_sync_collectives(instrs)
            for comp, instrs in per_comp.items()
            if any(c == comp for c, _ in sync_payload)}
        for (comp, name), (base, payload) in sync_payload.items():
            if pipelined_by_comp.get(comp, {}).get(name):
                pipelined_ops += 1
                overlapped += payload
                slot = kind_slot(base)
                slot["overlapped"] += payload
                slot["pipelined"] += 1
    return {
        "collective_bytes": total,
        "overlapped_bytes": overlapped,
        "overlap_pct": round(100.0 * overlapped / total, 2) if total
        else None,
        "async_ops": async_ops,
        "sync_ops": sync_ops,
        "pipelined_ops": pipelined_ops,
        "by_kind": by_kind,
    }


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

# Published per-chip peaks, keyed by the ``device_kind`` jax reports.
# "TPU v5 lite" is the v5e: 197 TFLOP/s bf16 and 819 GB/s of HBM (Google
# Cloud documentation, "TPU v5e"); the ICI figure is the 2 x 45 GB/s ring
# bandwidth the PR-6 collective model was built on (the published
# aggregate is 1,600 Gbit/s per chip over four links).
CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "ici_bytes_s": 90e9},
}


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Per-chip peak rates the roofline and every MFU normalize against,
    for the chip jax calls ``device_kind``.  A kind that is not in
    :data:`CHIP_PEAKS` raises: a CPU, or a chip nobody has looked up, gets
    no utilization figure rather than another chip's."""
    try:
        return dict(CHIP_PEAKS[device_kind])
    except KeyError:
        raise ValueError(
            "no published peaks for device kind %r (known: %s); add it to "
            "analysis.costmodel.CHIP_PEAKS with its source"
            % (device_kind, sorted(CHIP_PEAKS))) from None


def roofline(flops: float, hbm_bytes: float, collective_wire_bytes: float,
             peaks: Dict[str, float],
             measured_step_s: Optional[float] = None) -> Dict:
    """Peak-normalized component times and the binding roof, against the
    ``peaks`` of one chip (:func:`chip_peaks`).

    ``measured_step_s`` (when known) anchors the shares: each share is
    that component's lower-bound time over the measured step, and the
    residue the device math cannot explain is the host-bound share.
    Without a measurement the shares are relative to the slowest
    component (pure static mode)."""
    compute_s = flops / peaks["flops"] if peaks["flops"] else 0.0
    hbm_s = hbm_bytes / peaks["hbm_bytes_s"] if peaks["hbm_bytes_s"] \
        else 0.0
    coll_s = collective_wire_bytes / peaks["ici_bytes_s"] \
        if peaks["ici_bytes_s"] else 0.0
    comp = {"compute": compute_s, "hbm": hbm_s, "collective": coll_s}
    device_roof = max(comp.values())
    bound = max(comp, key=comp.get) if device_roof > 0 else "unknown"
    out = {"compute_s": compute_s, "hbm_s": hbm_s, "collective_s": coll_s,
           "device_roof_s": device_roof, "bound": bound,
           "peaks": {k: peaks[k] for k in
                     ("flops", "hbm_bytes_s", "ici_bytes_s")}}
    denom = measured_step_s if measured_step_s else device_roof
    if denom:
        shares = {k: round(v / denom, 4) for k, v in comp.items()}
        if measured_step_s:
            host = max(0.0, 1.0 - device_roof / measured_step_s)
            shares["host"] = round(host, 4)
            if host > 0.5:
                out["bound"] = "host"
            out["measured_vs_analytic"] = round(
                measured_step_s / device_roof, 3) if device_roof else None
        out["shares"] = shares
    return out


def decode_step_model(num_layers: int, hidden: int, vocab: int,
                      slots: int, cached_tokens: int,
                      quant_bits: int = 32) -> Dict[str, float]:
    """Analytic cost of ONE paged decode step (all slots, one token
    each) — the roofline the decode bench and servebench hold measured
    tokens/sec against.

    Decode is weights-bandwidth-bound: every step re-reads every matmul
    weight once (12·L·h² block weights + V·h head at ``quant_bits`` per
    value — weight-only quantization divides exactly this term) and the
    cached K/V once (``cached_tokens`` across all slots, f32 pages),
    while FLOPs are a thin 2·bytes multiply-accumulate over the same
    weights.  Returns flops / weight_bytes / kv_bytes / hbm_bytes per
    step; tokens-per-second roofline = slots / (hbm_bytes / HBM_GB/s).
    """
    h, L, V, S = int(hidden), int(num_layers), int(vocab), int(slots)
    matmul_params = 12 * L * h * h + V * h
    weight_bytes = matmul_params * quant_bits / 8.0 \
        + (V + (L * 4 + 2) * h) * 4.0          # embeddings + LN affine f32
    flops = 2.0 * S * matmul_params \
        + 4.0 * S * int(cached_tokens) / max(S, 1) * h * L  # attn qk+pv
    kv_bytes = 2.0 * L * int(cached_tokens) * h * 4.0      # read k+v
    kv_bytes += 2.0 * L * S * h * 4.0                      # this step's write
    return {"flops": flops, "weight_bytes": weight_bytes,
            "kv_bytes": kv_bytes,
            "hbm_bytes": weight_bytes + kv_bytes + S * V * 4.0}
