"""Executor — binds a Symbol to devices and runs it.

Reference: src/executor/graph_executor.cc (GraphExecutor::Init :512/:951,
Forward :81, Backward :94, RunOps :1469) + python/mxnet/executor.py.

TPU-native design: where the reference turns each graph node into one engine
op (InitCachedOps, graph_executor.cc:1221) and bulk-fuses segments
(InitOpSegs :1340), here the ENTIRE graph lowers to one pure JAX function —
forward is one jitted XLA computation, forward+backward another.  The nnvm
passes map as: Gradient → jax.vjp; InferShape → jax.eval_shape + param
hints; PlanMemory/DetectInplaceAddTo → XLA buffer assignment + donation;
PlaceDevice/ctx_group → sharding annotations (see parallel/).
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError, dtype_np, dtype_name
from .context import Context, cpu
from .ndarray.ndarray import NDArray, zeros as nd_zeros, array as nd_array
from .ops import shape_hints  # installs infer_params hooks  # noqa: F401
from .symbol.symbol import Node, NodeEntry, Symbol, _topo_order
from . import rng as _rng

__all__ = ["Executor", "GraphProgram", "infer_shapes", "infer_types",
           "set_backward_mirror", "backward_mirror_policy",
           "apply_backward_mirror"]


# ---------------------------------------------------------------------------
# Activation-memory mirroring (MXNET_BACKWARD_DO_MIRROR analog).
#
# The reference recomputes cheap forward nodes during backward instead of
# keeping their activations (src/executor/graph_executor.cc:253-311,
# docs/faq/env_var.md:89-94), trading ~30-50% activation memory for ~5% step
# time.  TPU-native analog: jax.checkpoint (remat) around the whole forward,
# with an XLA rematerialisation policy choosing what to keep:
#
#   'none'          - keep every activation (no remat)
#   'dots'          - keep matmul/conv outputs, recompute elementwise/norm
#                     chains (closest to the reference mirror heuristic)
#   'dots_no_batch' - keep only weight-style matmuls (no batch dims)
#   'full'          - keep nothing; recompute the entire forward in backward
#
# Selection: set_backward_mirror(policy) > MXNET_TPU_REMAT_POLICY >
# MXNET_BACKWARD_DO_MIRROR=1 (maps to 'dots').
# ---------------------------------------------------------------------------

_mirror_override: Optional[str] = None


def set_backward_mirror(policy: Optional[str]):
    """Select the activation-remat policy programmatically.

    policy: 'none' | 'dots' | 'dots_no_batch' | 'full' | None (None defers
    back to the MXNET_TPU_REMAT_POLICY / MXNET_BACKWARD_DO_MIRROR env vars).
    """
    global _mirror_override
    if policy is not None and policy not in _REMAT_POLICIES:
        raise ValueError("unknown remat policy %r (choose from %s)"
                         % (policy, sorted(_REMAT_POLICIES)))
    _mirror_override = policy


def backward_mirror_policy() -> str:
    """Resolve the active remat policy name."""
    import os
    if _mirror_override is not None:
        return _mirror_override
    env = os.environ.get("MXNET_TPU_REMAT_POLICY")
    if env:
        if env not in _REMAT_POLICIES:
            import warnings
            warnings.warn("MXNET_TPU_REMAT_POLICY=%r is not one of %s; "
                          "remat stays off" % (env, sorted(_REMAT_POLICIES)))
            return "none"
        return env
    if os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0") not in ("0", ""):
        return "dots"
    return "none"


def apply_backward_mirror(fn, policy: Optional[str] = None):
    """Public remat helper for raw-JAX training loops: wrap a pure forward
    (or loss) function so its activations are rematerialized during
    backward per `policy` (None = the currently active policy; see
    set_backward_mirror)."""
    return _remat_wrap(fn, policy if policy is not None
                       else backward_mirror_policy())


def _remat_wrap(fn, policy: str):
    """Wrap a pure forward fn in jax.checkpoint per the named policy."""
    if policy == "none":
        return fn
    xla_policy = _REMAT_POLICIES[policy]()
    if xla_policy is None:   # 'full': keep nothing (jax.checkpoint default)
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=xla_policy)


_REMAT_POLICIES = {
    "none": lambda: None,
    "full": lambda: None,
    "dots": lambda: jax.checkpoint_policies.dots_saveable,
    "dots_no_batch":
        lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def batch_hint_from(arg_map: Dict[str, Any], arg_names: Sequence[str]):
    """Leading-dim hint used to resolve 0-dims in creation-op shapes (the
    reference begin_state convention): the 'data' arg if present, else the
    first argument that has a shape."""
    if "data" in arg_map and hasattr(arg_map["data"], "shape"):
        return arg_map["data"].shape[0]
    for n in arg_names:
        v = arg_map.get(n)
        if hasattr(v, "shape") and v.shape:
            return v.shape[0]
    return None


def node_attrs(node, train: bool, batch_hint):
    """Attrs for evaluating one graph node: 0-dims resolved against the
    batch hint, _train injected for mode-dependent ops.  Single source of
    truth for GraphProgram.evaluate and placement.SegmentedProgram."""
    attrs = node.parsed_attrs()
    if not node.inputs and 0 in (attrs.get("shape") or ()):
        if not batch_hint:
            raise ValueError(
                "creation op %r has 0-dim shape %r but no batch hint is "
                "available to resolve it (bind with a 'data' input or a "
                "shaped argument)" % (node.op.name, attrs.get("shape")))
        attrs = type(attrs)(attrs)
        attrs["shape"] = tuple(batch_hint if d == 0 else d
                               for d in attrs["shape"])
    if node.op.mode_dependent:
        attrs = type(attrs)(attrs)
        attrs["_train"] = train
    return attrs


def _shard_constrain_outputs(out, ann, name):
    """Activation placement: a ``__shard__`` attr on an *op* node pins
    the op's outputs to a mesh spec via ``with_sharding_constraint``, so
    GSPMD anchors its propagation there instead of guessing (the
    placement-layer analog of the reference's per-node ctx_group).  The
    annotation grammar and the resolution both live in
    parallel/placement.py — one grammar for params AND activations.
    Inert (identity) unless a mesh is active (parallel.mesh
    .set_current_mesh — ShardedTrainer arms it around its traces), so
    single-device paths never pay for it."""
    from .placement import activation_constraint
    return activation_constraint(out, ann, name)


class GraphProgram:
    """A Symbol compiled into a pure function.

    fn(arg_arrays, aux_arrays, keys, train) evaluates the whole DAG.
    Shared by Executor, CachedOp (gluon) and Module's fused train step.
    """

    def __init__(self, symbol: Symbol):
        self.symbol = symbol
        self.nodes = _topo_order(symbol._entries)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        aux_ids = symbol._aux_var_ids()
        self.var_kind: Dict[int, str] = {}
        for n in self.nodes:
            if n.is_var:
                self.var_kind[id(n)] = "aux" if id(n) in aux_ids else "arg"
        # rng nodes, in topo order
        self.rng_nodes = [n for n in self.nodes
                          if not n.is_var and n.op.needs_rng]
        self.num_rng = len(self.rng_nodes)
        # aux writeback plan: list of (aux_name, node, out_idx)
        self.aux_updates = []
        for n in self.nodes:
            if n.is_var or not n.op.writeback:
                continue
            for i_in, i_out in n.op.writeback_map(n.parsed_attrs()).items():
                if i_in < len(n.inputs):
                    src = n.inputs[i_in].node
                    if src.is_var and id(src) in aux_ids:
                        self.aux_updates.append((src.name, n, i_out))

    def evaluate(self, arg_arrays: Sequence, aux_arrays: Sequence,
                 keys, train: bool):
        """Pure evaluation. Returns (outputs, new_aux)."""
        outputs, new_aux, _ = self._evaluate_impl(
            arg_arrays, aux_arrays, keys, train, tap=False)
        return outputs, new_aux

    def tap_names(self):
        """Names of every non-variable node output, in topo order — the
        per-node tap points the reference monitor sees
        (graph_executor.cc:121 invokes the callback on every op output)."""
        names = []
        for node in self.nodes:
            if node.is_var:
                continue
            n_vis = node.op.num_visible_outputs(node.parsed_attrs())
            if n_vis == 1:
                names.append(node.name + "_output")
            else:
                # multi-output nodes number every output, matching
                # Symbol.list_outputs ("<name>_output0", "<name>_output1", …)
                names.extend(node.name + "_output%d" % i
                             for i in range(n_vis))
        return names

    def _evaluate_impl(self, arg_arrays, aux_arrays, keys, train: bool,
                       tap: bool):
        arg_map = dict(zip(self.arg_names, arg_arrays))
        aux_map = dict(zip(self.aux_names, aux_arrays))
        batch_hint = batch_hint_from(arg_map, self.arg_names)
        key_idx = 0
        raw: Dict[int, tuple] = {}
        taps = []
        for node in self.nodes:
            if node.is_var:
                kind = self.var_kind[id(node)]
                val = arg_map[node.name] if kind == "arg" else aux_map[node.name]
                raw[id(node)] = (val,)
                continue
            attrs = node_attrs(node, train, batch_hint)
            ins = [raw[id(e.node)][e.index] for e in node.inputs]
            if node.op.needs_rng:
                ins = [keys[key_idx]] + ins
                key_idx += 1
            # a stable device-side name per node (metadata only): the
            # profiler's op_name path reads mx.<OpType>.<node>, and the
            # backward ops inherit it through jvp/transpose
            with jax.named_scope("mx.%s.%s" % (node.op.name, node.name)):
                out = node.op.fn(attrs, *ins)
                out = out if isinstance(out, tuple) else (out,)
                ann = node.attrs.get("__shard__") if node.attrs else None
                if ann is not None:
                    out = _shard_constrain_outputs(out, ann, node.name)
            raw[id(node)] = out
            if tap:
                taps.extend(out[:node.op.num_visible_outputs(attrs)])
        outputs = [raw[id(e.node)][e.index] for e in self.symbol._entries]
        new_aux = list(aux_arrays)
        aux_pos = {n: i for i, n in enumerate(self.aux_names)}
        for aux_name, node, i_out in self.aux_updates:
            new_aux[aux_pos[aux_name]] = raw[id(node)][i_out]
        return tuple(outputs), tuple(new_aux), tuple(taps)

    # jitted entry points -------------------------------------------------
    @functools.lru_cache(maxsize=None)
    def _jit_forward(self, train: bool):
        def f(args, aux, keys):
            return self.evaluate(args, aux, keys, train)
        return jax.jit(f)

    @functools.lru_cache(maxsize=None)
    def _jit_forward_tapped(self, train: bool):
        """Forward that also returns every node output (monitor support)."""
        def f(args, aux, keys):
            return self._evaluate_impl(args, aux, keys, train, tap=True)
        return jax.jit(f)

    def _jit_fwd_bwd(self, train: bool, grad_mask: tuple):
        """One XLA computation: outputs + grads of selected args + new aux."""
        return self._jit_fwd_bwd_impl(train, grad_mask,
                                      backward_mirror_policy())

    @functools.lru_cache(maxsize=None)
    def _jit_fwd_bwd_impl(self, train: bool, grad_mask: tuple, remat: str):
        def f(args, aux, keys, out_cots):
            diff_args = [a for a, m in zip(args, grad_mask) if m]

            def split_fn(diff):
                it = iter(diff)
                full = [next(it) if m else a for a, m in zip(args, grad_mask)]
                outs, new_aux = self.evaluate(full, aux, keys, train)
                return outs, new_aux

            (outs, new_aux), vjp = jax.vjp(_remat_wrap(split_fn, remat),
                                           diff_args)
            zero_aux = tuple(jnp.zeros_like(a) for a in new_aux)
            (grads,) = vjp((tuple(out_cots), zero_aux))
            return outs, new_aux, grads
        return jax.jit(f)


def _struct(shape, dtype="float32"):
    return jax.ShapeDtypeStruct(tuple(shape), dtype_np(dtype))


def _resolve_structs(symbol: Symbol, kwargs: Dict[str, Any],
                     type_dict=None, partial=False):
    """Bidirectional-ish shape inference: walk the graph forward, filling
    unknown parameter shapes via infer_params hooks (shape_hints.py), then
    output shapes via jax.eval_shape per node."""
    prog = GraphProgram(symbol)
    type_dict = type_dict or {}
    known: Dict[str, jax.ShapeDtypeStruct] = {}
    for k, v in (kwargs or {}).items():
        if v is None:
            continue
        if isinstance(v, jax.ShapeDtypeStruct):
            known[k] = v
        elif isinstance(v, (tuple, list)):
            known[k] = _struct(v, type_dict.get(k, "float32"))
        elif isinstance(v, NDArray):
            known[k] = _struct(v.shape, v.dtype)
    batch_hint = None
    for cand in ("data", "data0"):
        if cand in known:
            batch_hint = known[cand].shape[0] if known[cand].shape else None
            break
    if batch_hint is None and known:
        first = next(iter(known.values()))
        batch_hint = first.shape[0] if first.shape else None
    shapes: Dict[int, tuple] = {}  # node id -> tuple of output structs
    for node in prog.nodes:
        if node.is_var:
            if node.name in known:
                shapes[id(node)] = (known[node.name],)
            elif "__shape__" in node.attrs:
                import ast
                shp = ast.literal_eval(str(node.attrs["__shape__"]))
                if shp is None or any((d is None or d <= 0) for d in shp):
                    shapes[id(node)] = (None,)  # partially-known: infer
                else:
                    dt = type_dict.get(node.name,
                                       node.attrs.get("__dtype__", "float32"))
                    s = _struct(shp, dt)
                    known[node.name] = s
                    shapes[id(node)] = (s,)
            else:
                shapes[id(node)] = (None,)
            continue
        # same 0-dim policy as evaluation (node_attrs): fail at bind time,
        # not first forward, when a 0-dim cannot be resolved
        try:
            attrs = node_attrs(node, train=False, batch_hint=batch_hint)
        except ValueError:
            if partial:
                shapes[id(node)] = (None,) * node.num_outputs()
                continue
            raise
        in_structs = [shapes[id(e.node)][e.index] for e in node.inputs]
        hook = getattr(node.op, "infer_params", None)
        if hook is not None and any(s is None for s in in_structs):
            in_shapes = [tuple(s.shape) if s is not None else None
                         for s in in_structs]
            try:
                hints = hook(attrs, in_shapes)
            except Exception:
                hints = {}
            for idx, shp in hints.items():
                if idx < len(in_structs) and in_structs[idx] is None:
                    var_node = node.inputs[idx].node
                    dt = type_dict.get(var_node.name, None)
                    if dt is None:
                        dt = in_structs[0].dtype if in_structs[0] is not None \
                            else "float32"
                    s = _struct(shp, dt)
                    in_structs[idx] = s
                    if var_node.is_var:
                        known[var_node.name] = s
                        shapes[id(var_node)] = (s,)
        if any(s is None for s in in_structs):
            if partial:
                shapes[id(node)] = (None,) * node.num_outputs()
                continue
            missing = [node.inputs[i].node.name
                       for i, s in enumerate(in_structs) if s is None]
            raise MXNetError(
                "infer_shape: cannot determine shape of %s (inputs of node "
                "%s); provide it explicitly" % (missing, node.name))
        a2 = attrs
        if node.op.mode_dependent:
            a2 = type(attrs)(attrs)
            a2["_train"] = False
        ins = list(in_structs)
        if node.op.needs_rng:
            ins = [jax.ShapeDtypeStruct((2,), np.uint32)] + ins
        out = jax.eval_shape(functools.partial(node.op.fn, a2), *ins)
        shapes[id(node)] = tuple(out) if isinstance(out, (tuple, list)) \
            else (out,)
    return prog, known, shapes


def infer_shapes(symbol: Symbol, kwargs, partial=False):
    prog, known, shapes = _resolve_structs(symbol, kwargs, partial=partial)
    arg_shapes = [tuple(known[n].shape) if n in known else None
                  for n in prog.arg_names]
    out_shapes = []
    for e in symbol._entries:
        s = shapes[id(e.node)][e.index]
        out_shapes.append(tuple(s.shape) if s is not None else None)
    aux_shapes = [tuple(known[n].shape) if n in known else None
                  for n in prog.aux_names]
    return arg_shapes, out_shapes, aux_shapes


# ops whose output dtype follows a specific (non-first) input: lookup ops
# emit the dtype of their table, not of their integer indices
_DTYPE_FOLLOWS_INPUT = {"Embedding": 1, "take": 0, "gather_nd": 0}

# inputs pinned to a fixed dtype regardless of the data dtype: BatchNorm
# keeps gamma/beta and the moving stats float32 under fp16/bf16 data
# (reference batch_norm.cc type inference)
_DTYPE_PINNED_INPUTS = {"BatchNorm": {1: "float32", 2: "float32",
                                      3: "float32", 4: "float32"}}


def infer_types(symbol: Symbol, kwargs):
    """Type inference given arg dtypes (reference Symbol.infer_type,
    src/executor/infer_graph_attr_pass.cc).

    Forward dtype propagation through the graph: a node's output dtype is
    its declared ``dtype`` attr (Cast, creation ops) if present, else the
    dtype of the input it follows (first input for most ops — the
    reference's same-type constraint — with a small table for lookup ops
    like Embedding whose output follows the table, not the indices).
    Unknown variables encountered as other inputs of the node adopt that
    same dtype (params follow data), matching the reference's propagation
    of the data type into weights."""
    prog = GraphProgram(symbol)
    type_dict = {k: dtype_name(v) for k, v in (kwargs or {}).items()
                 if v is not None}   # None = "unknown, please infer"
    default_dt = next(iter(type_dict.values()), "float32")
    dts: Dict[int, tuple] = {}   # node id -> per-output dtype names
    for node in prog.nodes:
        if node.is_var:
            d = type_dict.get(node.name) or node.attrs.get("__dtype__")
            dts[id(node)] = (dtype_name(d) if d else None,)
            continue
        attrs = node.parsed_attrs()
        # only a USER-set dtype attr declares the output dtype — parsed
        # attrs fill schema defaults (topk/argsort carry dtype='float32'
        # by default while their runtime output follows the input)
        declared = node.attrs.get("dtype")
        in_dts = [dts[id(e.node)][e.index] for e in node.inputs]
        if not node.inputs:
            # creation op: its (possibly default) dtype param IS the output
            anchor = dtype_name(attrs.get("dtype") or default_dt)
        elif node.op.name in _DTYPE_FOLLOWS_INPUT:
            # lookup op: dtype comes from the table input ONLY — integer
            # indices must not donate their dtype to an untyped table;
            # fall back to the op's dtype param (Embedding), never to the
            # index dtype
            f = _DTYPE_FOLLOWS_INPUT[node.op.name]
            anchor = in_dts[f] if f < len(in_dts) and in_dts[f] is not None \
                else dtype_name(attrs.get("dtype") or "float32")
        else:
            anchor = next((d for d in in_dts if d is not None), default_dt)
        # untyped variable inputs adopt the node's anchor dtype (pinned
        # inputs — BN params/stats — keep their fixed dtype instead)
        pinned = _DTYPE_PINNED_INPUTS.get(node.op.name, {})
        for i, (e, d) in enumerate(zip(node.inputs, in_dts)):
            if d is None and e.node.is_var:
                dts[id(e.node)] = (pinned.get(i, anchor),)
        out_dt = dtype_name(declared) if declared else anchor
        dts[id(node)] = (out_dt,) * node.op.num_outputs(attrs)
    def _final(name_nodes):
        return [np.dtype(dtype_np(dts[id(n)][0] or default_dt))
                for n in name_nodes]
    by_name = {n.name: n for n in prog.nodes if n.is_var}
    arg_types = _final([by_name[n] for n in prog.arg_names])
    out_types = [np.dtype(dtype_np(dts[id(e.node)][e.index] or default_dt))
                 for e in symbol._entries]
    aux_types = [np.dtype(dtype_np(dts[id(by_name[n])][0] or "float32"))
                 for n in prog.aux_names]
    return arg_types, out_types, aux_types


def infer_storage_types(symbol: Symbol, kwargs):
    """Storage-type inference (reference Symbol.infer_storage_type over
    FInferStorageType, infer_graph_attr_pass.cc).

    Forward propagation of {'default','row_sparse','csr'} tags through
    the graph.  An op with a registered ``stype_rule`` (ops/
    sparse_storage.py) declares its output storage; every other op is a
    dense producer — sparse inputs densify at its edge, the reference's
    dense-fallback path.  Variables default to 'default' unless given in
    `kwargs` or tagged with a ``__storage_type__`` attr.

    Returns (arg_stypes, out_stypes, aux_stypes) as strings."""
    prog = GraphProgram(symbol)
    given = {k: v for k, v in (kwargs or {}).items() if v}
    sts: Dict[int, tuple] = {}
    for node in prog.nodes:
        if node.is_var:
            st = given.get(node.name) or \
                node.attrs.get("__storage_type__", "default")
            sts[id(node)] = (st,)
            continue
        in_sts = tuple(sts[id(e.node)][e.index] for e in node.inputs)
        rule = getattr(node.op, "stype_rule", None)
        attrs = node.parsed_attrs()
        if rule is not None:
            out = tuple(rule(attrs, in_sts))
            n_out = node.op.num_outputs(attrs)
            if len(out) < n_out:
                out = out + ("default",) * (n_out - len(out))
        else:
            out = ("default",) * node.op.num_outputs(attrs)
        sts[id(node)] = out
    by_name = {n.name: n for n in prog.nodes if n.is_var}
    arg_sts = [sts[id(by_name[n])][0] for n in prog.arg_names]
    out_sts = [sts[id(e.node)][e.index] for e in symbol._entries]
    aux_sts = [sts[id(by_name[n])][0] for n in prog.aux_names]
    return arg_sts, out_sts, aux_sts


class Executor:
    """Bound computation (reference python/mxnet/executor.py).

    forward() → one jitted XLA call; backward()/run_fwd_bwd() → one jitted
    XLA call computing outputs + gradients together.
    """

    def __init__(self, symbol: Symbol, ctx: Context,
                 args, args_grad=None, grad_req="write", aux_states=None,
                 shared_exec: Optional["Executor"] = None, program=None,
                 group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else cpu()
        if program is not None:
            self._prog = program
        elif shared_exec is not None and shared_exec._symbol is symbol:
            self._prog = shared_exec._prog
        else:
            self._prog = GraphProgram(symbol)
        arg_names = self._prog.arg_names

        if isinstance(args, dict):
            self.arg_arrays = [args[n] for n in arg_names]
        else:
            self.arg_arrays = list(args)
        self.arg_dict = dict(zip(arg_names, self.arg_arrays))

        aux_names = self._prog.aux_names
        if aux_states is None:
            aux_states = []
        if isinstance(aux_states, dict):
            self.aux_arrays = [aux_states[n] for n in aux_names]
        else:
            self.aux_arrays = list(aux_states)
        self.aux_dict = dict(zip(aux_names, self.aux_arrays))

        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(arg_names, grad_req))
        else:
            self.grad_req = {n: grad_req.get(n, "null") for n in arg_names}

        if args_grad is None:
            self.grad_arrays = [None] * len(arg_names)
        elif isinstance(args_grad, dict):
            self.grad_arrays = [args_grad.get(n) for n in arg_names]
        else:
            self.grad_arrays = list(args_grad)
            if len(self.grad_arrays) < len(arg_names):
                self.grad_arrays += [None] * (len(arg_names) -
                                              len(self.grad_arrays))
        self.grad_dict = {n: g for n, g in zip(arg_names, self.grad_arrays)}

        self.outputs: List[NDArray] = []
        self._monitor_callback = None
        self._monitor_all = False
        self._last_keys = None  # RNG keys of the last forward, for backward

        # ctx_group model parallelism: if the symbol carries grouped nodes
        # that map to a device other than the bind device, execute via the
        # segmented per-device program (placement.py) instead of one jit.
        self._seg = None
        if group2ctx:
            from .placement import SegmentedProgram, group_devices
            devs = group_devices(symbol, group2ctx)
            if devs and devs != {self._ctx.jax_device}:
                self._seg = SegmentedProgram(self._prog, group2ctx, self._ctx)

    # -- binding helpers -------------------------------------------------
    @staticmethod
    def simple_bind(symbol: Symbol, ctx, grad_req="write", type_dict=None,
                    shared_exec=None, group2ctx=None, **kwargs):
        prog, known, shapes = _resolve_structs(symbol, kwargs, type_dict)
        missing = [n for n in prog.arg_names if n not in known]
        if missing:
            raise MXNetError("simple_bind: could not infer shapes for %s"
                             % missing)
        args = {n: nd_zeros(tuple(known[n].shape),
                            dtype=np.dtype(known[n].dtype), ctx=ctx)
                for n in prog.arg_names}
        aux = {n: nd_zeros(tuple(known[n].shape),
                           dtype=np.dtype(known[n].dtype), ctx=ctx)
               for n in prog.aux_names}
        greq = grad_req if isinstance(grad_req, dict) else \
            {n: grad_req for n in prog.arg_names}
        grads = {n: nd_zeros(tuple(known[n].shape),
                             dtype=np.dtype(known[n].dtype), ctx=ctx)
                 for n in prog.arg_names if greq.get(n, "null") != "null"}
        return Executor(symbol, ctx, args, args_grad=grads, grad_req=greq,
                        aux_states=aux, program=prog, group2ctx=group2ctx)

    # -- execution -------------------------------------------------------
    def _keys(self):
        if self._prog.num_rng == 0:
            return jnp.zeros((0, 2), dtype=jnp.uint32)
        return jnp.stack([_rng.next_key() for _ in range(self._prog.num_rng)])

    def _commit(self, h):
        """Place an incoming array on this executor's device."""
        return jax.device_put(h, self._ctx.jax_device)

    def _seg_grads(self, gmap, mask):
        """Order the segmented-path grad dict per arg_names.  A masked arg
        that received no cotangent (disconnected from the loss) gets zeros,
        matching the _jit_fwd_bwd path, rather than keeping a possibly
        uninitialized grad buffer."""
        grads = []
        out_mask = []
        for n, m in zip(self._prog.arg_names, mask):
            if not m:
                out_mask.append(False)
                continue
            if n in gmap:
                grads.append(gmap[n])
                out_mask.append(True)
            elif self.grad_dict.get(n) is not None:
                tgt = self.grad_dict[n]
                grads.append(jnp.zeros(tuple(tgt.shape),
                                       dtype=np.dtype(tgt.dtype)))
                out_mask.append(True)
            else:
                # masked but no grad buffer to write — drop from the mask
                out_mask.append(False)
        return tuple(grads), tuple(out_mask)

    def _prof_tic(self):
        from . import profiler as _prof
        return time.perf_counter() * 1e6 if _prof.is_running() else None

    def _prof_toc(self, t0, suffix, results):
        """Record one timed executor-step event (true wall time: profile
        mode syncs on the result, matching the reference engine timing)."""
        if t0 is None:
            return
        from . import profiler as _prof
        jax.block_until_ready(results)
        name = (self._symbol.name or "graph") + suffix
        _prof.record_event(name, t0, time.perf_counter() * 1e6 - t0,
                           cat="symbolic")

    def _seg_forward(self, args, aux, keys, is_train):
        """Forward through the segmented (ctx_group) program; aux returned
        in aux_names order."""
        outs, new_aux_map, _ = self._seg.run(
            dict(zip(self._prog.arg_names, args)),
            dict(zip(self._prog.aux_names, aux)),
            keys, bool(is_train))
        return outs, tuple(new_aux_map[n] for n in self._prog.aux_names)

    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k in self.arg_dict:
                tgt = self.arg_dict[k]
                tgt._handle = self._commit(
                    v._handle if isinstance(v, NDArray) else jnp.asarray(v))
        args = tuple(a._handle for a in self.arg_arrays)
        aux = tuple(a._handle for a in self.aux_arrays)
        keys = self._keys()
        if is_train:
            # only a train forward defines the mask backward must reuse; an
            # interleaved eval forward (monitor/validation) must not clobber it
            self._last_keys = keys
        taps = None
        t0 = self._prof_tic()
        if self._seg is not None:
            outs, new_aux = self._seg_forward(args, aux, keys, is_train)
        elif self._monitor_active() and self._monitor_all:
            outs, new_aux, taps = self._prog._jit_forward_tapped(
                bool(is_train))(args, aux, keys)
        else:
            fn = self._prog._jit_forward(bool(is_train))
            outs, new_aux = fn(args, aux, keys)
        self._prof_toc(t0, "_forward", outs)
        if is_train:
            for nd_, na in zip(self.aux_arrays, new_aux):
                nd_._handle = na
        self.outputs = [NDArray(o) for o in outs]
        if self._monitor_active():
            self._fire_monitor(args, aux, keys, is_train, self.outputs,
                               taps=taps)
        return self.outputs

    def _write_grads(self, grads, mask):
        gi = iter(grads)
        for name, m in zip(self._prog.arg_names, mask):
            if not m:
                continue
            g = next(gi)
            tgt = self.grad_dict.get(name)
            if tgt is None:
                continue
            if self._seg is not None:
                # grads come back on their segment's device; the grad buffer
                # (and the optimizer update) live on the bind device
                g = self._commit(g)
            if self.grad_req[name] == "add":
                tgt._handle = tgt._handle + g.astype(tgt._handle.dtype)
            else:
                tgt._handle = g.astype(tgt._handle.dtype)

    def backward(self, out_grads=None, is_train=True):
        mask = tuple(self.grad_req.get(n, "null") != "null"
                     for n in self._prog.arg_names)
        if not any(mask):
            return
        args = tuple(a._handle for a in self.arg_arrays)
        aux = tuple(a._handle for a in self.aux_arrays)
        # Reuse the RNG keys of the preceding forward so dropout masks etc.
        # match between the forward outputs and these gradients (reference
        # reuses forward state); only draw fresh keys with no prior forward.
        keys = self._last_keys if self._last_keys is not None else self._keys()
        if out_grads is None:
            if self.outputs:
                cots = tuple(jnp.ones_like(o._handle) for o in self.outputs)
            else:
                structs = jax.eval_shape(self._prog._jit_forward(bool(is_train)),
                                         args, aux, keys)[0]
                cots = tuple(jnp.ones(s.shape, s.dtype) for s in structs)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cots = tuple(g._handle if isinstance(g, NDArray) else jnp.asarray(g)
                         for g in out_grads)
        t0 = self._prof_tic()
        if self._seg is not None:
            gm = dict(zip(self._prog.arg_names, mask))
            _, _, gmap = self._seg.run(dict(zip(self._prog.arg_names, args)),
                                       dict(zip(self._prog.aux_names, aux)),
                                       keys, bool(is_train),
                                       grad_mask=gm, out_cots=cots)
            grads, mask = self._seg_grads(gmap, mask)
        else:
            fn = self._prog._jit_fwd_bwd(bool(is_train), mask)
            _, _, grads = fn(args, aux, keys, cots)
        self._prof_toc(t0, "_backward", grads)
        self._write_grads(grads, mask)

    def run_fwd_bwd(self, out_cots=None, is_train=True):
        """Fused forward+backward: ONE XLA computation (the perf path used
        by Module).  Returns outputs; grads written per grad_req; aux
        updated."""
        mask = tuple(self.grad_req.get(n, "null") != "null"
                     for n in self._prog.arg_names)
        args = tuple(a._handle for a in self.arg_arrays)
        aux = tuple(a._handle for a in self.aux_arrays)
        keys = self._keys()
        self._last_keys = keys
        t0 = self._prof_tic()
        if not any(mask):
            if self._seg is not None:
                # aux handles live on segment devices after a segmented step;
                # the single-device jit would see mixed devices and either
                # fail or silently ignore placement
                outs, new_aux = self._seg_forward(args, aux, keys, is_train)
            else:
                outs, new_aux = self._prog._jit_forward(bool(is_train))(
                    args, aux, keys)
            grads = ()
        elif self._seg is not None:
            gm = dict(zip(self._prog.arg_names, mask))
            cots = None if out_cots is None else tuple(
                c._handle if isinstance(c, NDArray) else c for c in out_cots)
            outs, new_aux_map, gmap = self._seg.run(
                dict(zip(self._prog.arg_names, args)),
                dict(zip(self._prog.aux_names, aux)),
                keys, bool(is_train), grad_mask=gm, out_cots=cots)
            new_aux = tuple(new_aux_map[n] for n in self._prog.aux_names)
            grads, mask = self._seg_grads(gmap, mask)
        else:
            fn = self._prog._jit_fwd_bwd(bool(is_train), mask)
            if out_cots is None:
                structs = jax.eval_shape(self._prog._jit_forward(bool(is_train)),
                                         args, aux, keys)[0]
                cots = tuple(jnp.ones(s.shape, s.dtype) for s in structs)
            else:
                cots = tuple(c._handle if isinstance(c, NDArray) else c
                             for c in out_cots)
            outs, new_aux, grads = fn(args, aux, keys, cots)
        if is_train:
            for nd_, na in zip(self.aux_arrays, new_aux):
                nd_._handle = na
        self._prof_toc(t0, "_fwd_bwd", (outs, grads))
        self.outputs = [NDArray(o) for o in outs]
        if grads:
            self._write_grads(grads, mask)
        if self._monitor_active():
            self._fire_monitor(args, aux, keys, is_train, self.outputs)
        return self.outputs

    # -- misc API parity -------------------------------------------------
    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def export_compiled(self, path, input_names=("data",),
                        input_dtypes=None, append=False):
        """Write a serialized AOT deploy artifact (see deploy.py).

        The bound arg arrays become the artifact's weights; ``input_names``
        stay runtime inputs.  The result loads via
        deploy.ServedProgram.load (or the C ABI's MXPredCreateFromServed)
        and runs with no symbol layer or tracing."""
        from .deploy import export_compiled as _export
        unknown = [n for n in input_names if n not in self.arg_dict]
        if unknown:
            raise MXNetError("export_compiled: unknown inputs %s" % unknown)
        const_args = {n: arr.asnumpy() for n, arr in self.arg_dict.items()
                      if n not in input_names}
        aux = tuple(a._handle for a in self.aux_arrays)
        input_shapes = {n: self.arg_dict[n].shape for n in input_names}
        return _export(self._prog, const_args, aux, list(input_names),
                       input_shapes, path, input_dtypes, append=append)

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name]._handle = self._commit(
                    arr._handle.astype(self.arg_dict[name]._handle.dtype))
            elif not allow_extra_params:
                raise MXNetError("Found name \"%s\" not in arguments" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name]._handle = self._commit(
                        arr._handle.astype(self.aux_dict[name]._handle.dtype))
                elif not allow_extra_params:
                    raise MXNetError("Found name \"%s\" not in aux" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind for new input shapes; XLA re-specialises automatically
        (the reference's careful memory-sharing rebind is unnecessary —
        buffers are XLA-managed)."""
        new_args = {}
        for n, arr in self.arg_dict.items():
            if n in kwargs:
                new_args[n] = nd_zeros(kwargs[n], dtype=arr.dtype,
                                       ctx=self._ctx)
            else:
                new_args[n] = arr
        grads = {n: nd_zeros(new_args[n].shape, dtype=new_args[n].dtype,
                             ctx=self._ctx)
                 for n, g in self.grad_dict.items() if g is not None}
        return Executor(self._symbol, self._ctx, new_args, args_grad=grads,
                        grad_req=self.grad_req, aux_states=self.aux_dict,
                        program=self._prog)

    @property
    def ctx_group_devices(self):
        """Devices of the ctx_group segments, in execution order, or None
        when the graph runs unsegmented on one device (public view of the
        placement result — the PlaceDevice pass outcome)."""
        if self._seg is None:
            return None
        return [s.device for s in self._seg.segments]

    def set_monitor_callback(self, callback, monitor_all=False):
        """Install a (name, NDArray) callback fired after each forward.

        monitor_all=False taps only graph outputs; True taps EVERY node
        output (the reference graph_executor.cc:121 behavior) by running
        the instrumented forward program."""
        self._monitor_callback = callback
        self._monitor_all = monitor_all

    def _monitor_active(self):
        if self._monitor_callback is None:
            return False
        gate = getattr(self._monitor_callback, "monitor_active", None)
        return gate() if gate is not None else True

    def _fire_monitor(self, args, aux, keys, is_train, outs, taps=None):
        """Invoke the monitor callback on outputs, or on every node output
        when monitor_all.  taps: precomputed node outputs from a tapped
        forward; when absent under monitor_all an extra tapped forward runs
        (monitor is a debug tool and Monitor.tic gates it to every Nth
        batch)."""
        if self._monitor_all and self._seg is None:
            if taps is None:
                _, _, taps = self._prog._jit_forward_tapped(bool(is_train))(
                    args, aux, keys)
            for n, t in zip(self._prog.tap_names(), taps):
                self._monitor_callback(n, NDArray(t))
        else:
            for n, o in zip(self._symbol.list_outputs(), outs):
                self._monitor_callback(n, o)

    def debug_str(self):
        return self._symbol.debug_str()
