"""Ahead-of-time deployment artifacts: serialized compiled programs.

The TPU deploy unit (docs/deploy.md) is a compiled XLA executable plus
its weights — the analog of the reference's amalgamation predictor
(a single .so + symbol JSON + params blob).  ``export_compiled`` AOT-
compiles an inference program and writes ONE self-describing file in the
resilience container format (JSON header + raw numpy buffers + the
serialized-executable bytes as an opaque blob, CRC32 everywhere —
resilience/container.py).  There is NO pickle in the artifact: loading an
untrusted file parses JSON and copies buffers, and the loader explicitly
refuses pickle streams, so nothing in the container can execute code.
The executable payload itself is only handed to XLA's deserializer after
the container's integrity checks pass.

``ServedProgram.load`` deserializes and runs it WITHOUT the symbol
layer, graph builder, or any tracing — jax.experimental
.serialize_executable.deserialize_and_load hands back the executable
directly.  The input/output pytree structures are NOT stored in the file
(they would need pickle); they are reconstructed from the arity counts in
the header, which is possible because the compiled signature is always
``fwd(params_tuple, inputs_tuple) -> outputs_tuple``.  The C ABI reaches
this through MXPredCreateFromServed (capi.py pred_create_served), so a C
consumer can run a trained model from the artifact alone.

The interactive-decode deploy unit is the sibling
``serving/decode.DecodeProgram`` artifact: same container format and
device-fingerprint convention, but weights-only (optionally int8/int4
quantized) — its donated-KV step program cannot ride the serialized-
executable path (see mxnet_tpu/compile/cache.donation_safe) and
re-jits once at load instead.

Caveat (inherent to XLA AOT): the artifact is compiled for a specific
device kind + topology.  ``export_compiled`` records ``platform``,
``device_kind`` and ``device_count`` in the container header and
``ServedProgram.load`` refuses a mismatch with a typed
:class:`TopologyMismatch` — instead of an opaque XLA deserializer crash
— unless ``MXNET_TPU_SERVED_IGNORE_TOPOLOGY=1`` (experts: e.g. loading
a single-chip artifact on a larger host to inspect its header).
Artifacts written before these fields existed load with a warning.
"""
from __future__ import annotations

import logging
import os

import numpy as np

from .base import MXNetError
from .resilience.container import read_container, write_container

_MAGIC = "mxnet_tpu-served-v2"


class TopologyMismatch(MXNetError):
    """A served artifact was compiled for different hardware than the
    loading process sees (platform / device kind / device count)."""


def _current_topology():
    """(platform, device_kind, device_count) of the running backend."""
    import jax
    devices = jax.devices()
    return (jax.default_backend(), devices[0].device_kind, len(devices))


def device_fingerprint(topology=None) -> str:
    """The per-topology key an artifact's executable blobs are filed
    under: ``platform|device_kind|device_count``.  One artifact can
    carry an AOT executable per topology it may serve from (a 1-chip
    dev box, the tp2 serving slice, ...) — the loader picks the blob
    matching the running backend, so replica relaunches and rolling
    swaps deserialize a warm executable instead of refusing or
    compiling."""
    platform, kind, count = topology or _current_topology()
    return "%s|%s|%d" % (platform, kind, int(count))


def _to_host(arr):
    return np.asarray(arr)


def _arity_trees(n_params, n_inputs, n_outputs):
    """Rebuild the (in_tree, out_tree) pytree defs of the fixed compiled
    signature from arity counts alone — the pickle-free treedef story."""
    import jax
    in_tree = jax.tree_util.tree_structure(
        (((0,) * n_params, (0,) * n_inputs), {}))
    out_tree = jax.tree_util.tree_structure((0,) * n_outputs)
    return in_tree, out_tree


def export_compiled(prog, const_args, aux, input_names, input_shapes,
                    path, input_dtypes=None, append=False):
    """AOT-compile prog's inference forward and write the deploy bundle.

    ``prog`` is an executor GraphProgram; ``const_args`` maps non-input
    arg names to their (trained) values; ``aux`` is the aux-state tuple.
    The compiled program takes (params_tuple, inputs_tuple) so weights
    stay out of the executable and visible in the artifact.

    ``append=True`` adds THIS topology's executable to an existing
    artifact instead of overwriting it (refusing if weights or schema
    differ) — the per-topology AOT workflow: run the export once per
    deployment topology (dev chip, tp2 slice, ...) and ship ONE
    artifact whose loader picks the matching executable everywhere.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import serialize_executable

    input_dtypes = input_dtypes or {}
    param_names = [n for n in prog.arg_names if n not in input_names]
    missing = [n for n in param_names if n not in const_args]
    if missing:
        raise MXNetError("export_compiled: missing values for %s" % missing)
    arg_pos = {n: i for i, n in enumerate(prog.arg_names)}

    def fwd(param_vals, input_vals):
        args = [None] * len(prog.arg_names)
        for n, v in zip(param_names, param_vals):
            args[arg_pos[n]] = v
        for n, v in zip(input_names, input_vals):
            args[arg_pos[n]] = v
        keys = jnp.zeros((prog.num_rng, 2), jnp.uint32)
        outs, _ = prog.evaluate(args, tuple(aux), keys, False)
        return tuple(outs)

    def struct_of(value):
        host = np.asarray(value)
        return jax.ShapeDtypeStruct(host.shape, host.dtype)

    param_structs = tuple(struct_of(const_args[n]) for n in param_names)
    input_structs = tuple(
        jax.ShapeDtypeStruct(tuple(input_shapes[n]),
                             input_dtypes.get(n, np.float32))
        for n in input_names)
    out_structs = jax.eval_shape(fwd, param_structs, input_structs)
    compiled = jax.jit(fwd).lower(param_structs, input_structs).compile()
    payload, in_tree, out_tree = serialize_executable.serialize(compiled)

    # the loader rebuilds the treedefs from arity; prove at EXPORT time
    # that the reconstruction matches what serialize() actually saw, so a
    # mismatch fails loudly here, never at serving time
    want_in, want_out = _arity_trees(len(param_names), len(input_names),
                                     len(out_structs))
    if (want_in, want_out) != (in_tree, out_tree):
        raise MXNetError(
            "export_compiled: compiled pytree structure %r/%r is not the "
            "flat-tuple signature the served container encodes"
            % (in_tree, out_tree))

    platform, device_kind, device_count = _current_topology()
    fp = device_fingerprint()
    meta = {
        "magic": _MAGIC,
        "platform": platform,
        "device_kind": device_kind,
        "device_count": device_count,
        "param_names": param_names,
        "input_names": list(input_names),
        "input_shapes": {n: list(input_shapes[n]) for n in input_names},
        "input_dtypes": {n: np.dtype(input_dtypes.get(n, np.float32)).name
                         for n in input_names},
        "output_names": list(prog.out_names)
        if hasattr(prog, "out_names") else None,
        # static output schema: consumers size buffers before any forward
        "output_shapes": [list(s.shape) for s in out_structs],
        "output_dtypes": [np.dtype(s.dtype).name for s in out_structs],
        "n_outputs": len(out_structs),
        # per-topology executable directory: device fingerprint -> blob
        "topologies": {fp: "executable"},
    }
    arrays = {"param/%s" % n: _to_host(const_args[n]) for n in param_names}
    blobs = {"executable": payload}
    if append and os.path.exists(path):
        arrays, meta, blobs = _merge_topology(path, meta, arrays, payload,
                                              fp)
    write_container(path, arrays=arrays, meta=meta, blobs=blobs)
    return path


def _merge_topology(path, new_meta, new_arrays, payload, fp):
    """Fold THIS topology's executable into an existing artifact,
    refusing if the weights or the input/output schema differ — one
    artifact must mean one model, whatever it is compiled for."""
    arrays, meta, blobs = read_container(path)
    if meta.get("magic") != _MAGIC:
        raise MXNetError("%s is not a served-program artifact "
                         "(magic %r)" % (path, meta.get("magic")))
    for field in ("param_names", "input_names", "input_shapes",
                  "input_dtypes", "output_shapes", "output_dtypes",
                  "n_outputs"):
        if meta.get(field) != new_meta.get(field):
            raise MXNetError(
                "export_compiled(append=True): %s differs from the "
                "existing artifact (%r != %r) — refusing to mix models "
                "in one file" % (field, new_meta.get(field),
                                 meta.get(field)))
    for name, arr in new_arrays.items():
        if name not in arrays or not np.array_equal(
                np.asarray(arrays[name]), np.asarray(arr)):
            raise MXNetError(
                "export_compiled(append=True): weights %r differ from "
                "the existing artifact — refusing to mix models" % name)
    topo = dict(meta.get("topologies")
                or {device_fingerprint((meta.get("platform"),
                                        meta.get("device_kind"),
                                        meta.get("device_count") or 0)):
                    "executable"})
    blob_name = topo.get(fp) or ("executable@%s" % fp)
    topo[fp] = blob_name
    blobs = dict(blobs)
    blobs[blob_name] = payload
    meta = dict(meta)
    meta["topologies"] = topo
    return arrays, meta, blobs


def _check_topology(meta):
    """Refuse to hand a mismatched executable to XLA's deserializer.

    The deserializer's own failure mode is an opaque crash (or, worse, a
    program that runs and silently misbehaves on a different device
    kind); this check turns it into a typed, actionable error BEFORE the
    payload is touched."""
    if "platform" not in meta:      # pre-topology v2 artifact
        logging.warning(
            "served artifact predates topology metadata; cannot verify it "
            "matches this host (re-export to record platform/device_kind/"
            "device_count)")
        return
    recorded = (meta.get("platform"), meta.get("device_kind"),
                meta.get("device_count"))
    current = _current_topology()
    if recorded == current:
        return
    detail = ("artifact was exported for platform=%r device_kind=%r "
              "device_count=%r but this process sees platform=%r "
              "device_kind=%r device_count=%r" % (recorded + current))
    if os.environ.get("MXNET_TPU_SERVED_IGNORE_TOPOLOGY") == "1":
        logging.warning("MXNET_TPU_SERVED_IGNORE_TOPOLOGY=1: loading "
                        "anyway — %s", detail)
        return
    raise TopologyMismatch(
        "%s; XLA AOT executables only run on matching hardware "
        "(set MXNET_TPU_SERVED_IGNORE_TOPOLOGY=1 to override)" % detail)


def _select_executable(meta, blobs):
    """Pick the executable blob matching the running topology; returns
    ``(payload, result)`` with result ``hit`` (exact AOT match — the
    warm-load path), ``legacy`` (pre-fingerprint artifact) or ``forced``
    (operator override)."""
    topo = meta.get("topologies")
    if topo:
        fp = device_fingerprint()
        name = topo.get(fp)
        if name is not None and name in blobs:
            return blobs[name], "hit"
        if os.environ.get("MXNET_TPU_SERVED_IGNORE_TOPOLOGY") == "1":
            logging.warning(
                "MXNET_TPU_SERVED_IGNORE_TOPOLOGY=1: this process is %s "
                "but the artifact only carries %s — loading the primary "
                "executable anyway", fp, sorted(topo))
            return blobs["executable"], "forced"
        raise TopologyMismatch(
            "this process is %s but the artifact carries executables "
            "for %s; re-run export_compiled(append=True) on a matching "
            "host to add this topology (or set "
            "MXNET_TPU_SERVED_IGNORE_TOPOLOGY=1 to force the primary)"
            % (fp, sorted(topo)))
    # legacy artifact (one executable, topology fields at the top level
    # or absent): the v2 refuse-on-mismatch semantics, unchanged
    _check_topology(meta)
    recorded = (meta.get("platform"), meta.get("device_kind"),
                meta.get("device_count"))
    result = "hit" if recorded == _current_topology() else "legacy"
    return blobs["executable"], result


class ServedProgram:
    """A deserialized AOT executable + its weights; no tracing anywhere."""

    def __init__(self, arrays, meta, blobs):
        import jax
        from jax.experimental import serialize_executable
        if meta.get("magic") != _MAGIC:
            raise MXNetError("not a mxnet_tpu served-program file "
                             "(magic %r)" % meta.get("magic"))
        payload, self.load_result = _select_executable(meta, blobs)
        in_tree, out_tree = _arity_trees(
            len(meta["param_names"]), len(meta["input_names"]),
            int(meta["n_outputs"]))
        # export_compiled builds a one-device program
        from .compile.cache import program_devices
        self._compiled = serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=program_devices())
        self.input_names = meta["input_names"]
        self.input_shapes = {n: tuple(s)
                             for n, s in meta["input_shapes"].items()}
        self.input_dtypes = {n: np.dtype(d) for n, d
                             in meta["input_dtypes"].items()}
        self.output_names = meta.get("output_names")
        self.output_shapes = [tuple(s) for s in
                              meta.get("output_shapes") or []]
        self._params = tuple(jax.device_put(arrays["param/%s" % n])
                             for n in meta["param_names"])

    @classmethod
    def load(cls, path):
        from . import telemetry
        name = "ServedProgram(%s)" % os.path.basename(os.fspath(path))
        # compile/ span family: deserializing the AOT executable is this
        # path's compile point — it feeds the same compile.seconds
        # histogram and ungated ledger extra as the trainer's jit
        with telemetry.span("deploy/load", cat="deploy", path=str(path)), \
                telemetry.span("compile/served_load", cat="compile",
                               metric="compile.seconds",
                               timed=True) as _cs:
            arrays, meta, blobs = read_container(path)
            prog = cls(arrays, meta, blobs)
            # `hit` = an AOT executable for exactly this topology was in
            # the artifact (zero compile; the warm replica-relaunch /
            # rolling-swap path the fleet drills assert on)
            _cs.attrs["result"] = prog.load_result
        telemetry.tracing.note_compile(
            "served_load", _cs.duration,
            artifact=os.path.basename(os.fspath(path)),
            result=prog.load_result)
        telemetry.count("deploy.loads")
        # memory plane: served weights are a first-class HBM bucket (a
        # hot-swap briefly holds two models — the accounting shows it),
        # and the executable's breakdown feeds OOM forensics
        telemetry.memory.tag(prog._params, "served", label=name)
        if telemetry.memory.enabled():
            telemetry.memory.note_program(name, prog._compiled)
        # opt-in attribution of the serving program (static: the exec
        # side is measured by ServingRuntime's exec histogram instead)
        telemetry.perf.maybe_attribute(prog._compiled, name)
        return prog

    def forward(self, **inputs):
        """Run the compiled program; returns a list of host numpy outputs."""
        import jax
        from . import telemetry
        with telemetry.span("deploy/forward", cat="deploy",
                            metric="deploy.forward_seconds"):
            vals = []
            for n in self.input_names:
                if n not in inputs:
                    raise MXNetError("missing input %r" % n)
                host = np.asarray(inputs[n], self.input_dtypes[n]) \
                    .reshape(self.input_shapes[n])
                vals.append(jax.device_put(host))
            outs = self._compiled(self._params, tuple(vals))
            return [np.asarray(o) for o in outs]
