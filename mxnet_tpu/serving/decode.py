"""Interactive decode engine: paged KV cache + continuous token-level
batching over one compiled step program.

The batch-scoring runtime (:mod:`runtime`) packs whole requests into one
fixed ``fwd(params, inputs)`` dispatch; transformer *generation* under
that model re-runs full prefill per token — O(T²) work per sequence and
a fresh XLA program per (batch, length) shape.  This module is the
interactive half the TensorFlow system paper calls the core serving
split (PAPERS.md): a decode loop whose per-token step

* keeps K/V in a **paged cache**: one fixed physical page pool
  ``(L, 2, P, H, rows, lanes)`` (a page's ``(page, D)`` tokens packed
  lane-dense) plus per-slot page tables, so cache shapes
  NEVER change — the step program compiles exactly once, whatever
  sequence lengths come and go (the recompile-per-token trap is
  graphcheck rule GC307);
* touches the pool with Pallas kernels and nothing else: ``kv_write``
  puts each row's K/V at ``(page, offset)`` **in place** (the donated
  pool is aliased into the kernel's result), ``decode_attn``
  (:func:`~mxnet_tpu.ops.pallas_kernels.decode_attention_pool`) walks
  a slot's pages via scalar-prefetched indices for its own row, and
  ``chunk_attn`` (:func:`~mxnet_tpu.ops.pallas_kernels.chunk_attention`)
  for a block of its prompt rows at once; all take the whole
  six-axis pool as it lies (lane-dense pages,
  :meth:`DecodeConfig.pool_shape`), so the compiled step holds no
  slice, scatter or copy of anything pool-sized — or the XLA scatter /
  gather formulation over per-layer slices, which is what the CPU runs
  and GSPMD shards for tensor-parallel serving
  (``MXNET_TPU_PALLAS_DECODE``);
* runs **continuous token-level batching** (:class:`DecodeEngine`):
  a scheduler admits and retires sequences per STEP, so requests join
  and leave the running batch mid-generation — slot allocation from the
  page pool, prefill chunked into the running batch (a chunk of prompt
  rows a step beside the slots' own, or one token a slot a step),
  admission-queue priorities/eviction and deadlines preserved (a
  retired or evicted sequence can never late-OK: the Request future is
  one-shot); the loop keeps one step in flight — step n+1 is dispatched
  before step n's tokens are fetched, a decoding slot's next token fed
  forward on the device (``prev_tok``) — so the host's work hides
  behind the device's;
* takes a **many-token step**: the slots' own rows and a chunk of
  prompt rows under one fixed budget, compiled once, which the engine
  feeds in chunks under the same contract (a GPT-2 program on one TPU
  derives its budget from the shapes; 0 is one token a slot a step);
* is built for a model **family** (``DecodeConfig.family``): GPT-2's
  block is :class:`DecodeProgram` as described above; a latent-attention
  model is :class:`LatentDecodeProgram`, whose block
  ``models/sarvam_mla.py`` supplies: parameters and pool in
  ``DecodeConfig.dtype``, a pool of latent rows ``(L, P, page, W)``
  touched by ``latent_write`` and ``mla_attn``, routed experts in the
  step, and a budget the config names; a model of gated short
  convolutions beside grouped-query attention is
  :class:`HybridDecodeProgram`, whose block ``models/lfm2_moe.py``
  supplies: a step state that is the K/V pool of its attention layers
  alone (``kv_heads`` heads, a query group each, through ``kv_write``,
  ``decode_attn`` and ``chunk_attn``) beside a per-slot convolution
  state, donated together;
* optionally serves **weight-only quantized** matmuls (int8 / packed
  int4, per-channel scales, dequantization fused in the kernel —
  :func:`~mxnet_tpu.ops.pallas_kernels.quant_matmul`), selected at
  export time;
* exports with **NamedSharding over the unified mesh** (PR-10 placement
  grammar): ``mesh={"tp": k}`` shards attention heads, FFN hidden and
  the KV pool over ``tp`` so a model bigger than one device's budget
  serves from a tp slice — the per-axis collective audit
  (:func:`decode_tp_model_bytes`) proves the step moves only the
  analytic activation-reduction bytes.

Env knobs (docs/deploy.md "Interactive decode"):

=====================================  ==================================
``MXNET_TPU_DECODE_SLOTS``             decode batch width S (8)
``MXNET_TPU_DECODE_PAGE``              KV page size, tokens (64)
``MXNET_TPU_DECODE_PAGES``             physical pages in the pool
                                       (0 = full residency:
                                       1 + S·pages_per_seq)
``MXNET_TPU_DECODE_MAX_NEW``           default max new tokens (128)
``MXNET_TPU_PALLAS_DECODE``            decode-attention backend:
                                       ``1`` pallas / ``0`` xla /
                                       ``auto`` (autotune cache, else
                                       pallas on TPU)
=====================================  ==================================
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..base import MXNetError
from ..resilience import chaos
from ..resilience.container import read_container, write_container
from .errors import (DeadlineExceeded, ExecFailed, Overloaded,
                     ServingError, SwapFailed, TopologyMismatch)
from .request import Request
from .runtime import ServingRuntime, _env_int

__all__ = ["DecodeConfig", "PagePool", "DecodeProgram", "LatentDecodeProgram",
           "HybridDecodeProgram", "DecodeRequest", "DecodeEngine",
           "decode_param_shapes",
           "init_decode_params", "decode_tp_model_bytes", "program_class"]

_MAGIC = "mxnet_tpu-decode-v1"
# a step counts as stalled where it took over 5 x the exec EWMA and this
# many seconds, from one fetch's return to the next
_STALL_FLOOR_S = 0.020

# the model families a decode program exists for (``DecodeConfig.family``):
# models/transformer.py's GPT-2 block, one token a slot a step, a K/V pool by
# head; models/sarvam_mla.py's latent-attention block with routed experts, a
# many-token step over a pool of latent rows; models/lfm2_moe.py's short
# convolutions beside grouped-query attention with routed experts, a
# many-token step over a K/V pool of the attention layers and a per-slot
# convolution state
TRANSFORMER_LM = "transformer_lm"
SARVAM_MLA = "sarvam_mla"
LFM2_MOE = "lfm2_moe"

# rows of one tile of the v5e MXU: a derived budget fills whole tiles
_MXU_ROWS = 128

# weights the quantized export rewrites (per layer + the head); LN affine
# params, biases and embeddings stay f32 — they are O(hidden), noise next
# to the O(hidden²)/O(V·hidden) matmul weights the quantization targets
_QUANT_SUFFIXES = ("q", "k", "v", "proj", "ff1", "ff2")


class DecodeConfig:
    """Static geometry of one decode deployment — everything the step
    program's shapes depend on, so two programs with equal configs are
    swap-compatible."""

    __slots__ = ("vocab_size", "num_layers", "hidden", "heads",
                 "max_seq_len", "page_size", "max_seqs", "quantize",
                 "eos_id", "forward_len", "family", "dtype",
                 "prefill_tokens_per_step", "model", "kv_heads")

    def __init__(self, vocab_size, num_layers, hidden, heads,
                 max_seq_len, page_size=None, max_seqs=None,
                 quantize=None, eos_id=None, forward_len=None,
                 family=None, dtype=None, prefill_tokens_per_step=None,
                 model=None, kv_heads=None):
        # which model family's step this is (the program class is looked up
        # by it), the dtype of its parameters and pool, the prompt rows a
        # step takes beside the slots' own (0: one token a slot a step; None:
        # the program derives them from the shapes,
        # :meth:`DecodeProgram.derived_budget`), and the family's own
        # settings (a published config.json's keys)
        self.family = str(family or TRANSFORMER_LM)
        self.dtype = str(dtype or "float32")
        self.prefill_tokens_per_step = (
            None if prefill_tokens_per_step is None
            else int(prefill_tokens_per_step))
        self.model = dict(model) if model else None
        self.vocab_size = int(vocab_size)
        self.num_layers = int(num_layers)
        self.hidden = int(hidden)
        self.heads = int(heads)
        if self.hidden % self.heads:
            raise MXNetError("hidden %d not divisible by heads %d"
                             % (self.hidden, self.heads))
        # the K/V pool's heads: one query group of heads / kv_heads each
        self.kv_heads = int(kv_heads or heads)
        if self.heads % self.kv_heads:
            raise MXNetError("heads %d not divisible by kv_heads %d"
                             % (self.heads, self.kv_heads))
        self.max_seq_len = int(max_seq_len)
        self.page_size = int(page_size if page_size is not None
                             else _env_int("MXNET_TPU_DECODE_PAGE", 64))
        self.max_seqs = int(max_seqs if max_seqs is not None
                            else _env_int("MXNET_TPU_DECODE_SLOTS", 8))
        if quantize not in (None, "int8", "int4"):
            raise MXNetError("quantize must be None/'int8'/'int4', got %r"
                             % (quantize,))
        self.quantize = quantize
        self.eos_id = None if eos_id is None else int(eos_id)
        # the fixed prompt width of the batch `forward` surface (canary
        # runs, fleet batch mode) — independent of max_seq_len
        self.forward_len = int(forward_len if forward_len is not None
                               else min(8, self.max_seq_len))

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def pages_per_seq(self) -> int:
        return -(-self.max_seq_len // self.page_size)

    def pool_pages(self) -> int:
        """Physical pages in the pool: page 0 is the allocator's trash
        page (inactive slots write there, nothing reads it), the rest
        serve sequences.  Default = full residency for max_seqs."""
        n = _env_int("MXNET_TPU_DECODE_PAGES", 0)
        return int(n) if n > 0 else 1 + self.max_seqs * self.pages_per_seq

    def pool_shape(self) -> tuple:
        """The page pool of this geometry, as the family's program class
        lays it out (:meth:`DecodeProgram.pool_shape_of`)."""
        return program_class(self.family).pool_shape_of(self)

    def to_meta(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}

    @classmethod
    def from_meta(cls, meta) -> "DecodeConfig":
        return cls(**{k: meta.get(k) for k in cls.__slots__})

    def same_geometry(self, other) -> bool:
        return all(getattr(self, k) == getattr(other, k)
                   for k in self.__slots__ if k != "quantize")

    def describe(self) -> str:
        return ("%s %s L%d H%d heads%d%s V%d T%d page%d S%d%s%s"
                % (self.family, self.dtype, self.num_layers, self.hidden,
                   self.heads, "/%d" % self.kv_heads
                   if self.kv_heads != self.heads else "",
                   self.vocab_size, self.max_seq_len,
                   self.page_size, self.max_seqs,
                   "+%d" % self.prefill_tokens_per_step
                   if self.prefill_tokens_per_step else "",
                   " %s" % self.quantize if self.quantize else ""))


class PagePool:
    """Host-side physical-page allocator over the fixed device pool.

    Page 0 is reserved as the trash page: inactive slots put their
    (masked, never-read) K/V writes there, so the step program needs no
    control flow for slot liveness."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise MXNetError("page pool needs >= 2 pages, got %d"
                             % num_pages)
        self.num_pages = int(num_pages)
        self._free: List[int] = list(range(1, self.num_pages))
        self._lock = threading.Lock()

    @property
    def available(self) -> int:
        with self._lock:
            return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` pages or None (never a partial grant)."""
        with self._lock:
            if n > len(self._free):
                return None
            pages, self._free = self._free[:n], self._free[n:]
            return pages

    def free(self, pages: Sequence[int]):
        with self._lock:
            self._free.extend(int(p) for p in pages)


def decode_param_shapes(config: DecodeConfig) -> Dict[str, tuple]:
    """Name -> shape of every parameter the decode program of
    ``config.family`` consumes (:meth:`DecodeProgram.param_shapes_of` of the
    family's class)."""
    return program_class(config.family).param_shapes_of(config)


def init_decode_params(config: DecodeConfig, seed: int = 0,
                       scale: float = 0.02) -> Dict[str, np.ndarray]:
    """Random parameters in :func:`decode_param_shapes`, of the family
    ``config`` names — the decode
    program consumes a trained module's ``arg_params`` directly; this
    helper only exists for tests and benches that have no trained model
    at hand.  Float32 on the host whatever ``config.dtype``: the program
    casts what its family keeps in the serving dtype."""
    rs = np.random.RandomState(seed)

    def init(name, shape):
        if name.endswith("gamma"):
            return np.ones(shape, np.float32)
        if name.endswith(("beta", "bias")):
            return np.zeros(shape, np.float32)
        return (rs.randn(*shape) * scale).astype(np.float32)

    return {name: init(name, shape)
            for name, shape in decode_param_shapes(config).items()}


def decode_tp_model_bytes(config: DecodeConfig, tp: int,
                          itemsize: int = 4) -> dict:
    """Analytic per-step collective payloads of the tp-sharded decode
    step (the audit-side model a test holds the lowered HLO against):
    Megatron-style head/FFN sharding leaves TWO partial-sum reductions
    per layer — the attention projection and the FFN down-projection —
    each of the (rows, hidden) activation (the slots' rows and the
    config's prompt rows), and the row-sharded vocab head
    gathers the (S, vocab) logits back whole (a vocab the tp degree
    does not divide keeps a replicated head per the placement degrade
    rule, and the gather disappears).  Nothing else may move: weights
    and KV pages stay resident in their shards."""
    S, h = config.max_seqs, config.hidden
    R = S + (config.prefill_tokens_per_step or 0)
    out = {"all-reduce": 2 * config.num_layers * R * h * itemsize}
    if tp > 1 and config.vocab_size % tp == 0:
        out["all-gather"] = S * config.vocab_size * itemsize
    return out


def _quantize_params(params, config: DecodeConfig):
    """Rewrite the matmul weights to (int payload, per-channel scales)
    pairs; everything else passes through."""
    from ..ops import pallas_kernels as pk
    bits = 8 if config.quantize == "int8" else 4
    names = set()
    for i in range(config.num_layers):
        for s in _QUANT_SUFFIXES:
            names.add("l%d_%s_weight" % (i, s))
    names.add("head_weight")
    out = {}
    for k, v in params.items():
        if k in names:
            q, sc = pk.quantize_weight(np.asarray(v), bits)
            out[k + "#q"] = q
            out[k + "#scale"] = sc
        else:
            out[k] = np.asarray(v, np.float32)
    return out


def _build_mesh(mesh):
    """None | MeshSpec | {"tp": k} axes dict -> MeshSpec or None."""
    if mesh is None:
        return None
    if hasattr(mesh, "mesh"):
        return mesh
    from ..parallel.mesh import MeshSpec
    return MeshSpec.build(dict(mesh))


class _StepOperands:
    """What a step's call hands the runtime from the host: ONE int32
    vector, ``tokens | positions | seq_lens | phys | off | page_table``
    (and ``row_slot | out_row`` behind them for a many-token step) at
    static offsets that follow from the config alone.  One, because every
    host array a jitted call is given is a host-to-device transfer of its
    own and costs the calling thread as much whatever its size (0.13 ms
    on the v5e's runtime).  :meth:`pack` builds the vector on the host,
    :meth:`unpack` cuts it apart inside the jitted step by static
    slices."""

    def __init__(self, config: DecodeConfig):
        S = config.max_seqs
        R = S + config.prefill_tokens_per_step      # rows of one step
        shapes = [("tokens", (R,)), ("positions", (R,)), ("seq_lens", (S,)),
                  ("phys", (R,)), ("off", (R,)),
                  ("page_table", (S, config.pages_per_seq))]
        if config.prefill_tokens_per_step:
            shapes += [("row_slot", (R,)), ("out_row", (S,))]
        self.fields = []            # (name, shape, start, stop)
        at = 0
        for name, shape in shapes:
            n = int(np.prod(shape))
            self.fields.append((name, shape, at, at + n))
            at += n
        self.size = at

    def pack(self, *arrays) -> np.ndarray:
        """The operands, in the order of ``fields``, as one fresh host
        vector (a new one a step: the runtime may still be reading the
        last step's)."""
        if len(arrays) != len(self.fields):
            raise MXNetError("a step takes %s, got %d operands"
                             % ([f[0] for f in self.fields], len(arrays)))
        packed = np.empty(self.size, np.int32)
        for (_name, shape, start, stop), x in zip(self.fields, arrays):
            packed[start:stop].reshape(shape)[...] = x
        return packed

    def unpack(self, packed):
        return tuple(packed[start:stop].reshape(shape)
                     for _name, shape, start, stop in self.fields)


class DecodeProgram:
    """One compiled decode step + its weights + cache geometry.

    ``params``: the training graph's ``arg_params`` (name -> array,
    models/transformer naming).  ``mesh``: None, a MeshSpec, or an axes
    dict like ``{"tp": 2}`` — params and the KV pool are placed with
    ``NamedSharding`` over the unified mesh and the step runs under
    GSPMD (attention heads / FFN hidden / KV pool sharded over ``tp``).
    ``quantize`` (or ``config.quantize``): int8/int4 weight-only
    quantized matmuls, fixed at construction = "selected at export".

    The page pool (:meth:`DecodeConfig.pool_shape`, float32) is this
    family's state (another family's may hold more beside its pool,
    :class:`HybridDecodeProgram`): made by :meth:`fresh_cache`, donated to
    every step and handed back as the same buffer.  On one device with
    the Pallas backend the step writes and reads it through ``kv_write``,
    ``decode_attn`` and ``chunk_attn`` alone, where it lies; the XLA
    backend and the tp export scatter into and gather from per-layer
    slices, and XLA lays the pool out as it sees fit for that.

    The step takes ``max_seqs`` rows, one a slot, and
    ``prefill_tokens_per_step`` rows of prompt beside them (the MANY-TOKEN
    step, :class:`LatentDecodeProgram`'s too): each row with its slot,
    position and place in the pool, the chunk's rows in blocks of
    :attr:`chunk_block` whose live rows share a slot, the head on
    ``out_row``, one row a slot.  Where the config names no budget the
    program derives one (:meth:`derived_budget`); 0 is the one-token step.
    """

    FAMILY = TRANSFORMER_LM

    def __init__(self, params: Dict, config: DecodeConfig, *, mesh=None,
                 quantize=None, name="decode"):
        import jax

        if quantize is not None:
            config = DecodeConfig(**dict(config.to_meta(),
                                         quantize=quantize))
        if config.family != self.FAMILY:
            raise MXNetError(
                "%s builds the %s step; the config describes %s (%s)"
                % (type(self).__name__, self.FAMILY, config.family,
                   config.describe()))
        if config.prefill_tokens_per_step is None:
            config = DecodeConfig(**dict(
                config.to_meta(),
                prefill_tokens_per_step=self.derived_budget(config, mesh)))
        self._check_config(config, mesh)
        self.config = config
        self.name = name
        self.spec = _build_mesh(mesh)
        if self.spec is not None and config.heads % max(
                1, self.spec.axis_size("tp")):
            raise MXNetError("heads %d not divisible by tp=%d"
                             % (config.heads, self.spec.axis_size("tp")))
        host = {k: np.asarray(v) for k, v in params.items()}
        self._check_params(host)
        if config.quantize and not any("#q" in k for k in host):
            host = _quantize_params(host, config)
        self._params = {k: self._place_param(k, v) for k, v in host.items()}
        telemetry.memory.tag(list(self._params.values()), "served",
                             label="DecodeProgram(%s)" % name)
        # as a step's call hands them over: a flat tuple (a dict is sorted
        # and walked on every call)
        leaves, self._param_tree = jax.tree_util.tree_flatten(self._params)
        self._param_leaves = tuple(leaves)
        self.trace_count = 0          # bumps INSIDE the traced step: the
        # compile-once oracle (a retrace is a bug, not a slow path)
        self._operands = _StepOperands(config)
        self._jit_step = jax.jit(self._packed_step_fn(),
                                 donate_argnums=(1,))
        # what the step gets as prev_tok from a caller that has none: on
        # the device, as the step's own next_tokens are (a call hands over
        # one host array, the packed operands); under a mesh, placed as
        # those come back (replicated), or handing them in would be a
        # second executable
        self._no_prev_tok = jax.device_put(
            np.zeros(config.max_seqs, np.int32),
            None if self.spec is None else jax.sharding.NamedSharding(
                self.spec.mesh, jax.sharding.PartitionSpec()))
        self._compiled = False
        self._compile_lock = threading.Lock()
        # generic program surface (schema checks, canary, fleet batch
        # mode): one fixed (S, forward_len) token matrix in, next-token
        # ids out
        S = config.max_seqs
        self.input_names = ["tokens"]
        self.input_shapes = {"tokens": (S, config.forward_len)}
        self.input_dtypes = {"tokens": np.dtype(np.int32)}
        self.output_shapes = [(S, 1)]

    # -- what the family decides ------------------------------------------
    @staticmethod
    def pool_shape_of(config: DecodeConfig) -> tuple:
        """``(L, 2, P, H, rows, lanes)``: a page's ``(page_size,
        head_dim)`` tokens lie lane-dense in its last two axes,
        ``pallas_kernels.kv_pack`` of them to a row of 128 lanes (the
        row-major reshape of ``(page_size, head_dim)``), so that a
        head_dim of 64 pads nothing on the TPU and the device's own layout
        for the array is the row-major one the kernels read."""
        from ..ops.pallas_kernels import kv_pack
        pack = kv_pack(config.page_size, config.head_dim)
        return (config.num_layers, 2, config.pool_pages(), config.heads,
                config.page_size // pack, pack * config.head_dim)

    @staticmethod
    def param_shapes_of(config: DecodeConfig) -> Dict[str, tuple]:
        """The TRAINING graph's names and layouts
        (models/transformer.get_symbol)."""
        h, v, t = config.hidden, config.vocab_size, config.max_seq_len
        shapes = {"tok_embed_weight": (v, h), "pos_embed": (t, h),
                  "ln_f_gamma": (h,), "ln_f_beta": (h,),
                  "head_weight": (v, h), "head_bias": (v,)}
        for i in range(config.num_layers):
            p = "l%d_" % i
            for nm, shape in (("q", (h, h)), ("k", (h, h)), ("v", (h, h)),
                              ("proj", (h, h)), ("ff1", (4 * h, h)),
                              ("ff2", (h, 4 * h))):
                shapes[p + nm + "_weight"] = shape
                shapes[p + nm + "_bias"] = shape[:1]
            for ln in ("ln1", "ln2"):
                shapes[p + ln + "_gamma"] = (h,)
                shapes[p + ln + "_beta"] = (h,)
        return shapes

    @staticmethod
    def derived_budget(config: DecodeConfig, mesh=None) -> int:
        """The prompt rows a step takes where the config names none.  Where
        the step runs on one TPU through the Pallas kernels: the most whole
        chunk blocks that, with the slots' own rows, fill the fewest
        128-row tiles (the v5e MXU's rows) that hold one block beside them
        (96 at 32 slots, 64 at 64, 112 at 8).  Elsewhere (a mesh, the XLA
        formulation, the CPU) 0: one token a slot a step."""
        import jax
        from ..ops import pallas_kernels as pk
        S, block = config.max_seqs, pk.chunk_attn_rows()
        if (mesh is not None or jax.default_backend() != "tpu"
                or not pk.decode_backend_is_pallas(
                    S, config.heads, config.head_dim, config.page_size,
                    config.dtype)):
            return 0
        rows = -(-(S + block) // _MXU_ROWS) * _MXU_ROWS
        return (rows - S) // block * block

    @property
    def rows(self) -> int:
        """Rows of one step: the slots' own and the chunk's."""
        return self.config.max_seqs + self.config.prefill_tokens_per_step

    @property
    def chunk_block(self) -> int:
        """Rows of one block of the chunk: a slot's chunk rows start a
        block, and the live rows of a block are one slot's (the kernel's
        ``chunk_attn_rows()``)."""
        from ..ops.pallas_kernels import chunk_attn_rows
        return chunk_attn_rows()

    # -- construction helpers ---------------------------------------------
    @staticmethod
    def _check_config(config, mesh):
        from ..ops.pallas_kernels import chunk_attn_rows
        block = chunk_attn_rows()
        if config.dtype != "float32" or config.prefill_tokens_per_step % block:
            raise MXNetError(
                "the %s step serves in float32 and takes prompt rows in "
                "whole blocks of %d; the config asks for %s"
                % (TRANSFORMER_LM, block, config.describe()))

    def _check_params(self, host):
        need = {"tok_embed_weight", "pos_embed", "ln_f_gamma",
                "ln_f_beta", "head_weight", "head_bias"}
        for i in range(self.config.num_layers):
            p = "l%d_" % i
            for nm in _QUANT_SUFFIXES:
                need.add(p + nm + "_weight")
                need.add(p + nm + "_bias")
            for ln in ("ln1", "ln2"):
                need.add(p + ln + "_gamma")
                need.add(p + ln + "_beta")
        have = {k.split("#")[0] for k in host}
        missing = sorted(need - have)
        if missing:
            raise MXNetError("decode params missing %s (training-graph "
                             "names, models/transformer.get_symbol)"
                             % missing[:6])

    def _param_pspec(self, key):
        """PartitionSpec of one parameter under the tp recipe."""
        from jax.sharding import PartitionSpec as P
        base = key.split("#")[0]
        leaf = base.split("_", 1)[-1] if base.startswith("l") else base
        if base.startswith("l"):
            nm = base.split("_")[1]
            if nm in ("q", "k", "v", "ff1"):
                # row-parallel: output features sharded (= heads for
                # q/k/v since heads are contiguous head_dim blocks)
                if key.endswith("#scale") or leaf.endswith("bias"):
                    return P("tp")
                return P("tp", None)
            if nm in ("proj", "ff2"):
                # column-parallel: contraction dim sharded, partial sums
                # reduce across tp
                if key.endswith("#scale") or leaf.endswith("bias"):
                    return P()
                return P(None, "tp")
            return P()                      # layernorm affine
        if base == "head_weight" and not key.endswith("#scale"):
            return P("tp", None)            # vocab rows sharded
        # head bias/scales stay replicated: sharding them makes XLA
        # all-gather bias and product separately (two gathers where the
        # analytic model budgets one)
        return P()                          # embeddings, final LN, head

    def _place_param(self, key, value):
        import jax
        if self.spec is None:
            return jax.device_put(value)
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = self._param_pspec(key)
        # a dim the recipe would shard but the axis does not divide
        # degrades to replicated (e.g. an odd vocab head on tp2) — the
        # analytic model (decode_tp_model_bytes) mirrors this rule
        for dim, axis in enumerate(spec):
            if axis and np.asarray(value).shape[dim] % max(
                    1, self.spec.axis_size(axis)):
                spec = P()
                break
        return jax.device_put(value,
                              NamedSharding(self.spec.mesh, spec))

    def kv_sharding(self):
        if self.spec is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.spec.mesh,
                             P(None, None, None, "tp", None, None))

    def fresh_cache(self):
        """Zeroed page pool :meth:`DecodeConfig.pool_shape` on device (tp:
        sharded over heads).  The engine owns exactly one and threads it
        through every step (donated): the step hands back the same
        buffer, written in place."""
        import jax
        import jax.numpy as jnp
        z = jnp.zeros(self.config.pool_shape(), jnp.dtype(self.config.dtype))
        kv = jax.device_put(z, self.kv_sharding()) \
            if self.spec is not None else jax.device_put(z)
        telemetry.memory.tag(kv, "kv_cache",
                             label="DecodeProgram(%s).kv" % self.name)
        return kv

    @property
    def cache_bytes(self) -> int:
        """Bytes of the step's whole state (:meth:`fresh_cache`)."""
        import jax.numpy as jnp
        return int(np.prod(self.config.pool_shape())) \
            * jnp.dtype(self.config.dtype).itemsize + self.state_bytes

    @property
    def state_bytes(self) -> int:
        """Bytes of the state kept beside the page pool: none here."""
        return 0

    # the positions before its own that a row's state reads (0: none; the
    # step span's ``state_rows`` counts the rows that read them from a slot's
    # carried state)
    carried_taps = 0

    # -- the step program --------------------------------------------------
    def _make_step_fn(self, count=True):
        import jax
        import jax.numpy as jnp
        c = self.config
        H, Dh = c.heads, c.head_dim
        bits = 8 if c.quantize == "int8" else 4
        # under GSPMD the pallas kernels are partitioning black boxes:
        # the tp export always uses the XLA formulations (sharded by the
        # partitioner); single-device follows the knob/autotune cache
        sharded = self.spec is not None
        from ..ops import pallas_kernels as pk

        def lin(p, x, name):
            wq = p.get(name + "_weight#q")
            if wq is not None:
                y = pk.quant_matmul(x, wq, p[name + "_weight#scale"],
                                    bits,
                                    use_pallas=False if sharded else None)
            else:
                y = x @ p[name + "_weight"].T
            return y + p[name + "_bias"]

        def ln(p, x, name):
            x32 = x.astype(jnp.float32)
            mean = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.var(x32, axis=-1, keepdims=True)
            inv = jax.lax.rsqrt(var + 1e-5)
            return (x32 - mean) * inv * p[name + "_gamma"] \
                + p[name + "_beta"]

        # The pool's touches a layer, in two formulations.  Pallas: the
        # kernels take the pool whole and address it by prefetched scalars,
        # so the compiled step holds no slice, scatter or copy of anything
        # pool-sized and the donated pool is updated where it lies; the
        # slots' own rows attend through ``decode_attn``, the chunk's
        # through ``chunk_attn``.  XLA: a scatter and a gather over
        # per-layer slices of the pool seen as (L, 2, P, H, page, D), every
        # row against its slot's table — the form GSPMD can shard and the
        # CPU runs; XLA is free to re-lay the pool for it (on the v5e it
        # did, at two copies of the pool a step), so no chip cell runs it.
        by_token = (c.num_layers, 2, c.pool_pages(), H, c.page_size, Dh)

        def _write_xla(kv, i, k, v, phys, off):
            return pk.kv_write(kv, i, k, v, phys, off, use_pallas=False)

        def _attend_xla(q, kv, i, page_table, limit, row_slot):
            kv = kv.reshape(by_token)
            if row_slot is not None:
                page_table = page_table[row_slot]
            return pk.decode_attention(q, kv[i, 0], kv[i, 1], page_table,
                                       limit, use_pallas=False)

        def _attend_pallas(q, kv, i, page_table, limit, row_slot):
            if row_slot is None:
                return pk.decode_attention_pool(q, kv, i, page_table, limit)
            S = c.max_seqs
            return jnp.concatenate([
                pk.decode_attention_pool(q[:S], kv, i, page_table, limit[:S]),
                pk.chunk_attention(q[S:], kv, i, page_table, row_slot[S:],
                                   limit[S:], use_pallas=True)])

        _pool_ops_xla = (_write_xla, _attend_xla)
        _pool_ops_pallas = (pk.kv_write, _attend_pallas)

        def step(params, kv, tokens, positions, seq_lens, phys, off,
                 page_table, prev_tok=None, row_slot=None, out_row=None):
            # ONE trace, ever: shapes are fixed by the config, token
            # positions/lengths/page indices are all data (GC307)
            if count:
                self.trace_count += 1
            S = c.max_seqs
            R = tokens.shape[0]                 # S, or S + the chunk's rows
            # a pool handed over by token, (L, 2, P, H, page, D), is taken
            # and given back in that shape; fresh_cache's needs no reshape
            came_as = kv.shape
            kv = kv.reshape(c.pool_shape())
            # how the pool is written and read, decided once for both
            write, attend = (_pool_ops_pallas if not sharded
                             and pk.decode_backend_is_pallas(
                                 S, H, Dh, c.page_size, kv.dtype)
                             else _pool_ops_xla)
            # what a row attends: a slot's own row its slot's seq_lens; a
            # chunk row up to its own position; a dead row (position -1: a
            # slot in its prompt, a chunk's padding) nothing
            limit = seq_lens
            if row_slot is not None:
                live = positions >= 0
                limit = jnp.where(live, positions + 1, 0).astype(jnp.int32)
                limit = limit.at[:S].set(jnp.where(live[:S], seq_lens, 0))
            # stable device-side names (jax.named_scope: metadata only);
            # no layer index in them, so the layers group in a trace
            scope = jax.named_scope
            with scope("mx.decode.embed"):
                if prev_tok is not None:
                    # a negative token stands for "the one the last step
                    # produced for this slot", which never left the device
                    tokens = jnp.where(
                        tokens < 0,
                        prev_tok if row_slot is None else prev_tok[row_slot],
                        tokens)
                x = params["tok_embed_weight"][tokens] \
                    + params["pos_embed"][jnp.maximum(positions, 0)]
            for i in range(c.num_layers):
                pfx = "l%d_" % i
                with scope("mx.decode.ln"):
                    a = ln(params, x, pfx + "ln1")
                with scope("mx.decode.qkv"):
                    q = lin(params, a, pfx + "q").reshape(R, H, Dh)
                    k = lin(params, a, pfx + "k").reshape(R, H, Dh)
                    v = lin(params, a, pfx + "v").reshape(R, H, Dh)
                # the pool is touched by these and by nothing else
                with scope("mx.decode.kv_write"):
                    kv = write(kv, i, k, v, phys, off)
                with scope("mx.decode.attn"):
                    att = attend(q, kv, i, page_table, limit, row_slot)
                with scope("mx.decode.proj"):
                    att = lin(params, att.reshape(R, c.hidden),
                              pfx + "proj")
                    x = x + att
                with scope("mx.decode.ln"):
                    f = ln(params, x, pfx + "ln2")
                with scope("mx.decode.mlp"):
                    f = lin(params, f, pfx + "ff1")
                    f = jax.nn.gelu(f, approximate=False)
                    f = lin(params, f, pfx + "ff2")
                    x = x + f
            with scope("mx.decode.ln"):
                # one row a slot yields its token
                x = ln(params, x if out_row is None else x[out_row], "ln_f")
            with scope("mx.decode.head"):
                logits = lin(params, x, "head")           # (S, vocab)
                if sharded:
                    # the row-sharded vocab head leaves logits
                    # tp-sharded; gather them INSIDE the program (this
                    # is the one all-gather the analytic model budgets)
                    # so sampling and the host fetch see replicated
                    # values
                    from jax.sharding import NamedSharding, PartitionSpec
                    logits = jax.lax.with_sharding_constraint(
                        logits, NamedSharding(self.spec.mesh,
                                              PartitionSpec()))
            with scope("mx.decode.sample"):
                next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tok, logits, kv.reshape(came_as)

        return step

    def _packed_step_fn(self, count=True):
        """What is jitted: ``(param leaves, kv, packed, prev_tok)``, the
        host's operands as :class:`_StepOperands` packs them, cut apart on
        the device and handed to :meth:`_make_step_fn`'s function as the
        arguments it takes.  The parameters are arguments (the flat tuple
        ``_param_leaves``) and not closed over: a closed-over array is
        baked into the module as a constant (docs/deploy.md "One step in
        flight")."""
        import jax
        fn = self._make_step_fn(count)
        unpack = self._operands.unpack
        tree = self._param_tree

        def packed_step(leaves, kv, packed, prev_tok):
            (tokens, positions, seq_lens, phys, off, page_table,
             *rows) = unpack(packed)
            return fn(jax.tree_util.tree_unflatten(tree, leaves), kv, tokens,
                      positions, seq_lens, phys, off, page_table, prev_tok,
                      *rows)

        return packed_step

    def _zero_step_args(self):
        """A step's host operands in the order :class:`_StepOperands`
        packs them, for a step in which no slot is live (and every chunk
        row is dead)."""
        c = self.config
        S, R = c.max_seqs, self.rows
        i32 = np.int32
        table = np.zeros((S, c.pages_per_seq), i32)
        if not c.prefill_tokens_per_step:
            return (np.zeros(S, i32), np.zeros(S, i32), np.zeros(S, i32),
                    np.zeros(S, i32), np.zeros(S, i32), table)
        return (np.zeros(R, i32), np.full(R, -1, i32), np.zeros(S, i32),
                np.zeros(R, i32), np.zeros(R, i32), table, np.zeros(R, i32),
                np.arange(S, dtype=i32))

    def rows_of_slots(self, tokens, positions, phys, off):
        """A many-token step's per-row arrays for a caller with one row a
        slot: the chunk's rows dead.  Returns ``(tokens, positions, phys,
        off, row_slot, out_row)``."""
        S, C = self.config.max_seqs, self.config.prefill_tokens_per_step

        def rows(x, fill):
            return np.concatenate([np.asarray(x, np.int32).reshape(S),
                                   np.full(C, fill, np.int32)])

        slots = np.arange(S, dtype=np.int32)
        return (rows(tokens, 0), rows(positions, -1), rows(phys, 0),
                rows(off, 0), rows(slots, 0), slots)

    def _warm_args(self):
        """Everything the jitted step takes after the pool, all zeros."""
        return (self._operands.pack(*self._zero_step_args()),
                self._no_prev_tok)

    def handed_over(self, prev_tok=None) -> tuple:
        """``(host arrays, device arrays)`` that one call of :meth:`step`
        with this ``prev_tok`` hands the runtime: the packed operands (and
        ``prev_tok`` where a caller made it on the host); the parameters'
        leaves, the pool and a ``prev_tok`` that stayed on the device."""
        host_tok = isinstance(prev_tok, np.ndarray)
        return 1 + host_tok, len(self._param_leaves) + 2 - host_tok

    def _call(self, kv, operands, prev_tok):
        self.ensure_compiled()
        if prev_tok is None:
            prev_tok = self._no_prev_tok
        return self._jit_step(self._param_leaves, kv,
                              self._operands.pack(*operands), prev_tok)

    def step(self, kv, tokens, positions, seq_lens, phys, off,
             page_table, prev_tok=None, row_slot=None, out_row=None):
        """One decode step for every slot; returns ``(next_tokens (S,),
        logits (S, V), kv')`` (a family may return more after them).
        ``kv`` is DONATED — the caller must thread the returned pool into
        the next call.  Where a row's token is negative the row is fed
        ``prev_tok`` of its slot: hand in the last step's ``next_tokens``
        as it came back and a decoding slot's token need not pass through
        the host (:class:`DecodeEngine`).  The jitted step always gets the
        array (zeros when the caller has none), so there is one trace and
        one executable either way, and the host arrays reach it as one
        (:class:`_StepOperands`).

        A many-token step takes, per row, ``tokens``, ``positions`` (-1: a
        dead row), ``phys`` / ``off`` and ``row_slot``; per slot
        ``seq_lens[i]``, the cache positions slot i's own row attends, and
        ``out_row[i]``, the row that yields its token.  Without
        ``row_slot`` the arrays are one row a slot (the one-token
        signature) and the chunk rides dead (:meth:`rows_of_slots`)."""
        if self.config.prefill_tokens_per_step and row_slot is None:
            tokens, positions, phys, off, row_slot, out_row = \
                self.rows_of_slots(tokens, positions, phys, off)
        rows = () if row_slot is None else (row_slot, out_row)
        return self._call(kv, (tokens, positions, seq_lens, phys, off,
                               page_table) + rows, prev_tok)

    def ensure_compiled(self):
        """Compile the step once, visibly: the first build rides a
        ``compile/decode_step`` span + :func:`telemetry.tracing
        .note_compile`, so 'zero compiles after warmup' is provable from
        the same ``compile/*`` span family the trainer and the elastic
        drills use."""
        if self._compiled:
            return
        with self._compile_lock:
            if self._compiled:
                return
            kv = self.fresh_cache()
            with telemetry.span("compile/decode_step", cat="compile",
                                metric="compile.seconds", timed=True,
                                program=self.name) as sp:
                out = self._jit_step(self._param_leaves, kv,
                                     *self._warm_args())
            import jax
            jax.block_until_ready(out[0])
            telemetry.tracing.note_compile("decode_step", sp.duration,
                                           program=self.name,
                                           config=self.config.describe())
            self._compiled = True

    def lowered_step_text(self) -> str:
        """Optimized HLO of the step program (collective audits, GC307
        companions)."""
        import jax
        lowered = jax.jit(self._packed_step_fn(count=False)).lower(
            self._param_leaves, self.fresh_cache(), *self._warm_args())
        return lowered.compile().as_text()

    # -- generic batch surface (canary, fleet batch mode) ------------------
    def forward(self, tokens):
        """Fixed-shape batch surface: prefill each row of ``tokens``
        ((S, forward_len) int32) through the step program on a scratch
        cache and return the next-token ids ``(S, 1)``.  This is the
        swap-canary / ServingRuntime-compatible face of the program; the
        interactive path is :class:`DecodeEngine`."""
        c = self.config
        toks = np.asarray(tokens, np.int32).reshape(c.max_seqs,
                                                    c.forward_len)
        S = c.max_seqs
        pages_needed = -(-c.forward_len // c.page_size)
        if 1 + S * pages_needed > c.pool_pages():
            raise ServingError("forward_len %d needs %d pages > pool %d"
                               % (c.forward_len, S * pages_needed,
                                  c.pool_pages()))
        table = np.zeros((S, c.pages_per_seq), np.int32)
        for s in range(S):
            table[s, :pages_needed] = 1 + s * pages_needed \
                + np.arange(pages_needed)
        kv = self.fresh_cache()
        if c.prefill_tokens_per_step:
            return [self._forward_chunked(kv, toks, table).reshape(S, 1)]
        nxt = None
        for t in range(c.forward_len):
            pos = np.full(S, t, np.int32)
            out = self.step(
                kv, toks[:, t], pos, pos + 1,
                table[np.arange(S), t // c.page_size],
                np.full(S, t % c.page_size, np.int32), table)
            nxt, kv = out[0], out[2]
        return [np.asarray(nxt).reshape(S, 1)]

    def _forward_chunked(self, kv, toks, table):
        """``forward``'s prompts through a many-token step's chunk rows, a
        slot's rows starting a block, as many steps as the budget needs;
        each slot's token from the step that took its last row."""
        c = self.config
        S, C, page = c.max_seqs, c.prefill_tokens_per_step, c.page_size
        block, n = self.chunk_block, c.forward_len
        fed = np.zeros(S, np.int32)
        nxt = np.zeros(S, np.int32)
        while (fed < n).any():
            tokens, positions, seq_lens, phys, off, _t, row_slot, out_row = \
                self._zero_step_args()
            at, ended = 0, []
            for s in np.flatnonzero(fed < n):
                if at >= C:
                    break
                k = min(n - fed[s], C - at)
                rows = slice(S + at, S + at + k)
                pos = fed[s] + np.arange(k)
                tokens[rows], positions[rows] = toks[s, pos], pos
                phys[rows], off[rows] = table[s, pos // page], pos % page
                row_slot[S + at:S + at + -(-k // block) * block] = s
                at += -(-k // block) * block
                fed[s] += k
                seq_lens[s] = fed[s]
                if fed[s] == n:
                    out_row[s] = rows.stop - 1
                    ended.append(s)
            out = self.step(kv, tokens, positions, seq_lens, phys, off, table,
                            None, row_slot, out_row)
            kv = out[2]
            nxt[ended] = np.asarray(out[0])[ended]
        return nxt

    # -- export / load ------------------------------------------------------
    def export(self, path) -> str:
        """Write the per-topology deploy artifact: weights (quantized
        payloads included), config, and the device fingerprint + mesh
        axes it was built for.  No executable blob and no pickle — the
        loader re-jits through the one-compile step path (XLA:CPU
        executables with donated inputs do not survive serialization;
        see mxnet_tpu/compile/cache.donation_safe)."""
        from ..deploy import _current_topology, device_fingerprint
        platform, kind, count = _current_topology()
        meta = {
            "magic": _MAGIC,
            "config": self.config.to_meta(),
            "platform": platform, "device_kind": kind,
            "device_count": count,
            "topologies": {device_fingerprint(): "params"},
            "mesh_axes": (dict(self.spec.mesh.shape)
                          if self.spec is not None else None),
            "param_names": sorted(self._params),
        }
        arrays = {"param/%s" % k: np.asarray(v)
                  for k, v in self._params.items()}
        write_container(path, arrays=arrays, meta=meta, blobs={})
        return path

    @classmethod
    def load(cls, path, mesh="artifact", name=None):
        """Load an exported decode artifact.  ``mesh="artifact"``
        re-forms the mesh axes recorded at export (requiring the same
        device count on this host — typed :class:`TopologyMismatch`
        otherwise); pass an explicit mesh/axes dict or None to override.
        """
        arrays, meta, _blobs = read_container(path)
        if meta.get("magic") != _MAGIC:
            raise MXNetError("%s is not a decode artifact (magic %r)"
                             % (path, meta.get("magic")))
        config = DecodeConfig.from_meta(meta["config"])
        # the artifact says which family's step its weights are for: the
        # class that builds that step loads it, whichever class was asked
        if cls.FAMILY != config.family:
            cls = program_class(config.family)
        axes = meta.get("mesh_axes")
        if mesh == "artifact":
            mesh = axes
        if mesh:
            import jax
            need = 1
            for v in dict(mesh).values():
                need *= int(v)
            have = len(jax.devices())
            if need > have:
                raise TopologyMismatch(
                    "artifact was exported for mesh %s (%d devices) but "
                    "this process sees %d" % (dict(mesh), need, have))
        params = {k[len("param/"):]: v for k, v in arrays.items()
                  if k.startswith("param/")}
        stored = {v.dtype.name for v in params.values() if v.dtype.kind
                  not in "iu"} - {"float32"}
        if stored - {config.dtype}:
            raise MXNetError(
                "%s holds %s parameters but its config says %s: refusing to "
                "cast an artifact into another precision"
                % (path, sorted(stored), config.describe()))
        prog = cls(params, config, mesh=mesh,
                   name=name or os.path.basename(os.fspath(path)))
        telemetry.count("deploy.loads")
        return prog


class LatentDecodeProgram(DecodeProgram):
    """The decode program of a latent-attention family
    (``models/sarvam_mla.py`` supplies the block; nothing of it is written
    here): parameters and pool in ``config.dtype``, a pool of latent rows
    ``(L, P, page, W)`` and a MANY-TOKEN step under one fixed budget of rows,
    compiled once: ``max_seqs`` rows that are one a slot (a decoding slot's
    token) and ``prefill_tokens_per_step`` rows of prompt, each row with its
    slot, position and place in the pool, in blocks of
    ``pallas_kernels.mla_chunk_rows()`` whose live rows share a slot.  The
    step returns one next token a slot (the head runs on ``out_row``, one
    row a slot) and, fourth, what the expert layers saw: held picks and
    experts touched.  One device, no quantization."""

    FAMILY = SARVAM_MLA

    @staticmethod
    def _block():
        """The module that supplies the family's block: ``param_shapes``,
        ``model_of``, ``is_float32_param`` and ``Decoder``."""
        from ..models import sarvam_mla
        return sarvam_mla

    @staticmethod
    def pool_shape_of(config: DecodeConfig) -> tuple:
        """``(L, P, page, W)``: one row a token a layer (the normed latent
        and the rope key, no head axis, no K/V pair), W its width in whole
        tiles of 128 lanes."""
        from ..ops.pallas_kernels import latent_row_lanes
        return (config.num_layers, config.pool_pages(), config.page_size,
                latent_row_lanes(config.model["kv_lora_rank"]
                                 + config.model["qk_rope_head_dim"]))

    @classmethod
    def param_shapes_of(cls, config: DecodeConfig) -> Dict[str, tuple]:
        block = cls._block()
        return block.param_shapes(block.model_of(config.model),
                                  config.num_layers, config.vocab_size)

    @classmethod
    def _check_config(cls, config, mesh):
        block = cls._chunk_rows()
        if mesh is not None or config.quantize:
            raise MXNetError("the %s step runs on one device, unquantized"
                             % cls.FAMILY)
        if config.prefill_tokens_per_step < block \
                or config.prefill_tokens_per_step % block:
            raise MXNetError(
                "prefill_tokens_per_step %d is not a positive multiple of "
                "the chunk block (%d rows)"
                % (config.prefill_tokens_per_step, block))

    @staticmethod
    def derived_budget(config: DecodeConfig, mesh=None) -> int:
        """0: the latent step's budget is the config's to name (and its
        check refuses none)."""
        return 0

    @staticmethod
    def _chunk_rows() -> int:
        from ..ops.pallas_kernels import mla_chunk_rows
        return mla_chunk_rows()

    @property
    def chunk_block(self) -> int:
        """Rows of one block of the chunk (the attention kernel's: here
        ``mla_chunk_rows()``)."""
        return self._chunk_rows()

    def _check_params(self, host):
        want = decode_param_shapes(self.config)
        missing = sorted(set(want) - set(host))
        if missing:
            raise MXNetError("decode params missing %s (names of "
                             "models/%s.param_shapes)"
                             % (missing[:6], self.FAMILY))
        wrong = [(k, tuple(host[k].shape), want[k]) for k in want
                 if tuple(host[k].shape) != tuple(want[k])]
        if wrong:
            raise MXNetError("decode params of another shape than %s "
                             "needs: %s" % (self.config.describe(),
                                            wrong[:3]))

    def _place_param(self, key, value):
        import jax
        import jax.numpy as jnp
        dtype = jnp.float32 if self._block().is_float32_param(key) \
            else jnp.dtype(self.config.dtype)
        return jax.device_put(np.asarray(value).astype(dtype, copy=False))

    def _make_step_fn(self, count=True):
        c = self.config
        decoder = self._block().Decoder(
            c.model, num_layers=c.num_layers, vocab_size=c.vocab_size,
            slots=c.max_seqs, chunk_rows=c.prefill_tokens_per_step,
            dtype=c.dtype)

        def step(params, kv, tokens, positions, seq_lens, phys, off,
                 page_table, prev_tok, row_slot, out_row):
            # ONE trace, ever: the budget of rows is fixed by the config,
            # which rows are live and whose they are is data (GC307)
            if count:
                self.trace_count += 1
            return decoder.step(params, kv, tokens, positions, seq_lens,
                                phys, off, page_table, prev_tok, row_slot,
                                out_row)

        return step


class HybridDecodeProgram(LatentDecodeProgram):
    """The decode program of a family with two kinds of layer, gated short
    convolutions and grouped-query attention (``models/lfm2_moe.py``
    supplies the block), under :class:`LatentDecodeProgram`'s contract: one
    device, parameters in ``config.dtype``, the many-token step with its
    chunk in blocks of ``chunk_attn_rows()``, expert counts as the step's
    fourth result.  Its state is a dict donated whole to every step:

    * ``kv``: the K/V pool of the ATTENTION layers only, ``(n_attn, 2, P,
      kv_heads, rows, lanes)`` in ``config.dtype``, touched by
      ``kv_write``, ``decode_attn`` and ``chunk_attn`` with a query group
      of ``heads / kv_heads`` a key/value head;
    * ``conv``: every slot's last ``conv_L_cache`` convolution inputs a
      convolution layer, ``(n_conv, S, L, hidden)``, which the step reads
      and writes by position alone (a new request's rows read zeros where
      its positions are negative), so the engine does nothing for it."""

    FAMILY = LFM2_MOE

    @staticmethod
    def _block():
        from ..models import lfm2_moe
        return lfm2_moe

    @staticmethod
    def _chunk_rows() -> int:
        from ..ops.pallas_kernels import chunk_attn_rows
        return chunk_attn_rows()

    @staticmethod
    def pool_shape_of(config: DecodeConfig) -> tuple:
        """``(n_attn, 2, P, kv_heads, rows, lanes)``: the lane-dense pages of
        :meth:`DecodeProgram.pool_shape_of`, for the attention layers."""
        from ..models import lfm2_moe
        from ..ops.pallas_kernels import kv_pack
        hd = config.hidden // config.heads
        pack = kv_pack(config.page_size, hd)
        return (lfm2_moe.attention_layers(config.model, config.num_layers),
                2, config.pool_pages(), config.kv_heads,
                config.page_size // pack, pack * hd)

    @classmethod
    def _check_config(cls, config, mesh):
        super()._check_config(config, mesh)
        want = config.model["num_key_value_heads"]
        if config.kv_heads != want:
            raise MXNetError("%s has %d key/value heads; the config says %d"
                             % (config.describe(), want, config.kv_heads))

    def _state_shape(self) -> tuple:
        c = self.config
        return self._block().state_shape(c.model, c.num_layers, c.max_seqs)

    @property
    def carried_taps(self) -> int:
        return self.config.model["conv_L_cache"] - 1

    def fresh_cache(self):
        """The zeroed state: ``{"kv": pool, "conv": convolution state}``."""
        import jax
        import jax.numpy as jnp
        dtype = jnp.dtype(self.config.dtype)
        kv = jax.device_put(jnp.zeros(self.config.pool_shape(), dtype))
        conv = jax.device_put(jnp.zeros(self._state_shape(), dtype))
        telemetry.memory.tag(kv, "kv_cache",
                             label="HybridDecodeProgram(%s).kv" % self.name)
        telemetry.memory.tag(conv, "kv_cache",
                             label="HybridDecodeProgram(%s).conv" % self.name)
        return {"kv": kv, "conv": conv}

    @property
    def state_bytes(self) -> int:
        """Bytes of the convolution state alone."""
        import jax.numpy as jnp
        return int(np.prod(self._state_shape())) \
            * jnp.dtype(self.config.dtype).itemsize


_PROGRAMS = {TRANSFORMER_LM: DecodeProgram, SARVAM_MLA: LatentDecodeProgram,
             LFM2_MOE: HybridDecodeProgram}


def program_class(family: str):
    """The class that builds the decode step of ``family``."""
    try:
        return _PROGRAMS[family]
    except KeyError:
        raise MXNetError("no decode program for model family %r (has %s)"
                         % (family, sorted(_PROGRAMS))) from None


class DecodeRequest(Request):
    """One generation request: a prompt, a token budget, the shared
    deadline/priority semantics, and a one-shot future delivering the
    generated ids."""

    __slots__ = ("prompt", "max_new", "generated", "token_times", "tenant")

    def __init__(self, prompt, max_new, priority=0, deadline=None,
                 seq=-1):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ServingError("empty prompt")
        super().__init__({"tokens": prompt}, 1, priority=priority,
                         deadline=deadline, seq=seq)
        self.prompt = prompt
        self.max_new = int(max_new)
        self.generated: List[int] = []
        # one time.monotonic() per generated token, stamped by the engine
        # as it takes the token in (``serve/retire``): with
        # ``enqueued_at`` / ``t_dispatched`` an operator has queue wait,
        # time to first token and inter-token gaps per request
        self.token_times: List[float] = []
        self.tenant = None

    @property
    def n_prompt(self) -> int:
        return int(self.prompt.size)


class _Slot:
    """Host-side state of one occupied decode slot."""

    __slots__ = ("req", "pages", "pos")

    def __init__(self, req: DecodeRequest, pages: List[int]):
        self.req = req
        self.pages = pages
        # tokens fed so far (prompt + generated), counting every step
        # DISPATCHED for the slot, fetched or not
        self.pos = 0


class _InFlight:
    """One dispatched step whose tokens the host has not taken in yet."""

    __slots__ = ("seq", "takers", "attended", "pages", "overlapped",
                 "next_tok", "guards", "t_dispatch", "expert_counts",
                 "host_operands", "prev_ready", "ready")

    def __init__(self, seq, takers, attended, pages, overlapped,
                 host_operands):
        self.seq = seq
        # (slot index, request, takes a token?, its last by length?, prompt
        # rows counted as prefilled) of every slot the step ran for: the
        # REQUEST, because by the time the tokens arrive the slot may be
        # somebody else's
        self.takers = takers
        self.attended = attended        # sum of the step's seq_lens
        self.pages = pages              # and of the pages they lie on
        self.overlapped = overlapped    # dispatched behind another step
        self.host_operands = host_operands  # host arrays its call hands over
        self.next_tok = None            # the step's out[0], as handed back
        # a many-token step's out[3] ([held picks, experts touched]), or None
        self.expert_counts = None
        self.guards = None              # watchdog watch + OOM guard, open
        # what the host knew (``_is_ready``): at this step's dispatch, had
        # the step in flight ended (the device had nothing queued); at this
        # step's fetch, had it ended itself (the host came late)
        self.prev_ready = 0
        self.ready = 0
        self.t_dispatch = time.perf_counter()


def _us(seconds: float) -> int:
    return int(round(1e6 * seconds))


def _is_ready(arr) -> bool:
    """Has the device produced ``arr``, as far as the host knows?  Something
    without ``is_ready`` (a test's numpy result) counts as ready."""
    probe = getattr(arr, "is_ready", None)
    return probe is None or bool(probe())


class DecodeEngine(ServingRuntime):
    """Continuous token-level batching inside the serving runtime.

    The worker loop is a per-STEP scheduler instead of the batch
    packer: every iteration it retires finished/expired/cancelled
    sequences (freeing their pages), admits queued requests into free
    slots (allocating pages up front so a running sequence can never
    starve mid-generation; a higher-priority arrival may EVICT the
    cheapest running sequence when the pool is exhausted), then runs ONE
    decode step for all occupied slots — prefill is chunked into the
    running batch (a budget of prompt rows a step beside the slots' own,
    :meth:`_build_rows`, or one token a slot a step), so a long prompt
    never stalls other tenants' token cadence.  Admission, breaker, watchdog-armed
    dispatch, and the one-shot Request future (no late OKs, ever) are
    inherited from :class:`ServingRuntime`.

    The loop keeps **one step in flight**: iteration k dispatches step k
    and only then fetches step k-1's tokens, so the device always has its
    next step queued and the host's admit / build / dispatch / retire and
    the fetch's latency run beside the device's work.  What makes that
    possible: everything a step needs but a decoding slot's token follows
    from ``slot.pos``, which advances at dispatch; the token itself stays
    on the device (``tokens[i] = -1`` and the last step's ``next_tokens``
    handed to :meth:`DecodeProgram.step` as ``prev_tok``).  Bookkeeping
    therefore happens twice a step.  At dispatch: positions advance, the
    step's :class:`_InFlight` record notes which request sat in which
    slot, and a sequence that ends by length gives up its slot and pages
    at once.  At fetch, one iteration later: tokens go to the record's
    requests, counters and stamps are taken, futures settle.  A request
    found settled by then (swept, evicted, cancelled, failed, or ended on
    ``eos_id``, which is only seen a step late) has its token dropped,
    uncounted."""

    def __init__(self, program, *, max_new_default=None, **kw):
        prog = self._load_program(program)
        if not isinstance(prog, DecodeProgram):
            raise ServingError("DecodeEngine needs a DecodeProgram, got %r"
                               % (type(prog).__name__,))
        c = prog.config
        self._slots: List[Optional[_Slot]] = [None] * c.max_seqs
        self._pool = PagePool(c.pool_pages())
        self._kv = None
        self._flight: Optional[_InFlight] = None
        self._prev_tok = None     # out[0] of the last step dispatched
        self._t_fetched = 0.0     # perf_counter at the last fetch's return
        self._table = np.zeros((c.max_seqs, c.pages_per_seq), np.int32)
        self._max_new_default = int(
            max_new_default if max_new_default is not None
            else _env_int("MXNET_TPU_DECODE_MAX_NEW", 128))
        self._occ_hist = telemetry.Histogram(
            "decode.occupancy", registered=False, always=True)
        self._ttft_hist = telemetry.Histogram(
            "decode.ttft_seconds", registered=False, always=True)
        self._itl_hist = telemetry.Histogram(
            "decode.itl_seconds", registered=False, always=True)
        kw.setdefault("name", "decode")
        super().__init__(prog, **kw)
        # compile BEFORE serving (one visible compile/decode_step span;
        # the loop itself never compiles — GC307's invariant) and, under
        # MXNET_TPU_PREFLIGHT=1, statically prove it
        prog.ensure_compiled()
        self._maybe_preflight(prog)
        self._kv = prog.fresh_cache()

    # -- admission ----------------------------------------------------------
    def submit(self, tokens=None, *, max_new_tokens=None, priority=0,
               deadline=None, **_ignored) -> DecodeRequest:
        """Admit one generation request; returns its
        :class:`DecodeRequest` future (``result()`` -> ``[ids]``)."""
        if self._stop:
            raise ServingError("engine is closed")
        c = self._program.config
        prompt = np.asarray(tokens, np.int32).reshape(-1)
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self._max_new_default)
        if max_new < 1:
            raise ServingError("max_new_tokens must be >= 1, got %d"
                               % max_new)
        if prompt.size + max_new > c.max_seq_len:
            raise ServingError(
                "prompt %d + max_new %d exceeds max_seq_len %d"
                % (prompt.size, max_new, c.max_seq_len))
        with self._lock:
            self._counters["submitted"] += 1
            self._seq += 1
            seq = self._seq
        if not self._breaker.admit_ok():
            with self._lock:
                self._counters["shed_circuit"] += 1
            telemetry.count("serve.shed", cause="circuit")
            from .errors import CircuitOpen
            raise CircuitOpen("circuit open; shedding until the %.1fs "
                              "cooldown probe succeeds"
                              % self._breaker.cooldown)
        rel = self._default_deadline if deadline is None else deadline
        abs_deadline = (time.monotonic() + rel
                        if rel is not None and rel > 0 else None)
        req = DecodeRequest(prompt, max_new, priority=priority,
                            deadline=abs_deadline, seq=seq)
        self._queue.offer(req)
        with self._lock:
            self._counters["admitted"] += 1
        return req

    def generate(self, tokens, *, max_new_tokens=None, priority=0,
                 deadline=None) -> np.ndarray:
        """Synchronous submit + wait; returns the generated ids."""
        req = self.submit(tokens, max_new_tokens=max_new_tokens,
                          priority=priority, deadline=deadline)
        wait = None if req.deadline is None else req.remaining() + 5.0
        return req.result(timeout=wait)[0]

    # -- scheduler ----------------------------------------------------------
    def _active(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is not None]

    def _pages_for(self, req: DecodeRequest) -> int:
        c = self._program.config
        return -(-(req.n_prompt + req.max_new) // c.page_size)

    def _release_slot(self, idx: int):
        slot = self._slots[idx]
        if slot is None:
            return
        self._slots[idx] = None
        self._table[idx, :] = 0
        self._pool.free(slot.pages)

    def _retire(self, idx: int, error: BaseException):
        """Fail one running sequence: free its slot and pages, settle its
        future.  A step in flight for it finds it settled and drops its
        token."""
        slot = self._slots[idx]
        if slot is None:
            return
        self._release_slot(idx)
        self._settle(slot.req, error)

    def _settle(self, req: DecodeRequest,
                error: Optional[BaseException] = None):
        """Settle a request's future exactly once (the loser of the race
        is a no-op — a retired or evicted sequence can never late-OK)."""
        now = time.monotonic()
        req.t_exec_done = now
        delivered = False
        if error is not None:
            req._fail(error)
        else:
            delivered = req._deliver(
                [np.asarray(req.generated, np.int32)])
        with self._lock:
            self._counters["retired"] += 1
            if delivered:
                self._counters["completed"] += 1
        if delivered and req.latency is not None:
            self._lat_hist.observe(req.latency)
        outcome = "ok" if delivered else "late"
        telemetry.count("serve.requests", outcome=outcome)
        # the request's life on its stamps, inside the serve/retire or
        # serve/admit that settles it (not ``serve/request``: that is the
        # batch runtime's retrospective lane)
        attrs = {"n_prompt": req.n_prompt, "n_generated": len(req.generated),
                 "total_us": _us(now - req.enqueued_at),
                 "outcome": outcome if error is None
                 else type(error).__name__}
        if req.t_dispatched is not None:
            attrs["queue_wait_us"] = _us(req.t_dispatched - req.enqueued_at)
        times = req.token_times
        if times:
            attrs["ttft_us"] = _us(times[0] - req.enqueued_at)
        if len(times) > 1:
            attrs["itl_max_us"] = _us(max(
                b - a for a, b in zip(times, times[1:])))
        with telemetry.span("serve/request_done", cat="serve", **attrs):
            pass

    def _sweep_slots(self):
        """Pre-step pass: drop sequences that are already settled (a
        fleet hedge won elsewhere / caller cancelled) or past deadline."""
        for i in self._active():
            req = self._slots[i].req
            if req.done:
                self._release_slot(i)
                with self._lock:
                    self._counters["retired"] += 1
            elif req.expired():
                self._retire(i, DeadlineExceeded(
                    "deadline passed after %d/%d tokens"
                    % (len(req.generated), req.max_new)))

    def _admit_one(self, req: DecodeRequest) -> bool:
        """Place ``req`` in a free slot, evicting strictly-cheaper
        running sequences while slot or page pressure demands it (same
        victim order as the admission queue: lowest priority, then
        oldest; the victim's future settles with a typed
        :class:`Overloaded` NOW, so it can never late-OK).  False ->
        caller re-queues the arrival."""
        need = self._pages_for(req)

        def cheapest_victim():
            cands = [i for i in self._active()
                     if self._slots[i].req.priority < req.priority]
            if not cands:
                return None
            return min(cands, key=lambda i: (self._slots[i].req.priority,
                                             self._slots[i].req
                                             .enqueued_at))

        pages = None
        while True:
            free = [i for i, s in enumerate(self._slots) if s is None]
            if free:
                pages = self._pool.alloc(need)
                if pages is not None:
                    break
            v = cheapest_victim()
            if v is None:
                return False
            self._retire(v, Overloaded(
                "evicted mid-generation by a priority-%d arrival "
                "(decode %s pressure)" % (req.priority,
                                          "page" if free else "slot")))
            with self._lock:
                self._counters["evicted_slots"] += 1
            telemetry.count("serve.shed", cause="evicted")
        idx = free[0]
        slot = _Slot(req, pages)
        self._slots[idx] = slot
        self._table[idx, :] = 0
        self._table[idx, :len(pages)] = pages
        req.t_dispatched = time.monotonic()
        self._qwait_hist.observe(req.t_dispatched - req.enqueued_at)
        with self._lock:
            self._counters["admitted_slots"] += 1
        return True

    def _admit_from_queue(self):
        # the queue head gets an admission attempt EVERY step, even with
        # all slots occupied — that is the preemption window where a
        # high-priority arrival may evict a cheaper running sequence
        while True:
            req = self._queue.pop_live(timeout=0)
            if req is None:
                return
            if req.done:
                continue
            if not self._admit_one(req):
                self._queue.push_front(req)
                return

    def _run(self):
        while not self._stop:
            try:
                # the host loop's spans (serve/admit, serve/build,
                # serve/decode_step > serve/dispatch + serve/fetch,
                # serve/retire) are what a profiler trace attributes the
                # device's idle time between steps to; an iteration that
                # finds no work emits serve/admit alone
                with telemetry.span("serve/admit", cat="serve",
                                    queued=len(self._queue)) as sp:
                    before = self._counters["admitted_slots"]
                    self._sweep_slots()
                    self._admit_from_queue()
                    sp.annotate(admitted=self._counters["admitted_slots"]
                                - before)
                active = self._active()
                if active and self._breaker.dispatch_ok():
                    self._engine_step(active)
                elif self._flight is not None:
                    # nothing to dispatch behind the step in flight (every
                    # sequence's last step is out, or the circuit is
                    # open): take it in now, not after a sleep
                    self._drain()
                elif active:
                    time.sleep(0.02)
                else:
                    req = self._queue.pop_live(timeout=0.05)
                    if req is not None:
                        self._queue.push_front(req)
            except Exception:
                if not self._stop:
                    raise
                return

    def _engine_step(self, active: List[int]):
        """Dispatch one step for ``active``, then take in the step that
        was in flight while it was built."""
        with self._lock:
            self._batch_seq += 1
            seq = self._batch_seq
            prog = self._program
        c = prog.config
        S = c.max_seqs
        flight = self._flight
        with telemetry.span("serve/build", cat="serve", slots=len(active)):
            build = (self._build_rows if c.prefill_tokens_per_step
                     else self._build_one_token)
            step_args, takers, attention = build(active, c)
            seq_lens = step_args[2]
            # the table as this step saw it: releases and admissions
            # rewrite self._table while the step may still be reading
            table = self._table.copy()
            # a sequence whose last step this is needs no other: its slot
            # and pages go to the next admission now (the device runs
            # steps in order, so a new owner's step writes a page only
            # after this one has read it); its future waits for the token
            for i, _req, _takes, last, _rows in takers:
                if last:
                    self._release_slot(i)
            n_decode = sum(t[2] for t in takers)
            # what the call hands the runtime: host arrays it must transfer
            # (the packed operands: 1) and device arrays (parameters, pool,
            # the last step's tokens)
            host_operands, device_args = prog.handed_over(self._prev_tok)
            new = _InFlight(seq, takers, int(seq_lens.sum()),
                            int((-(-seq_lens // c.page_size)).sum()),
                            flight is not None, host_operands)
        try:
            # the span is the outermost, so that arming the watchdog and
            # the OOM guard are host time a trace can name; the two cover
            # the step from here to its fetch, one iteration on.
            # n_prefill: the prompt rows the step does not answer with a
            # token (one-token step: the slots still in their prompt)
            with telemetry.span(
                    "serve/decode_step", cat="serve", batch=seq,
                    slots=len(active), n_prefill=sum(t[4] for t in takers),
                    n_decode=n_decode, attended=new.attended,
                    in_flight=int(new.overlapped),
                    **attention) as step_span:
                with contextlib.ExitStack() as guards:
                    if self._exec_timeout is not None:
                        guards.enter_context(self._ensure_watchdog().watch(
                            "%s.step" % self._name, kind="step", step=seq,
                            timeout=self._exec_timeout))
                    guards.enter_context(telemetry.memory.oom_guard(
                        "%s.step" % self._name, step=seq))
                    chaos.maybe_exec_error(seq)
                    chaos.maybe_slow_exec(seq)
                    chaos.maybe_replica_crash(seq)
                    chaos.maybe_hedge_lag(seq)
                    if flight is not None:
                        # 1: this step goes to a device that, as far as the
                        # host knows, has nothing queued
                        new.prev_ready = int(_is_ready(flight.next_tok))
                        step_span.annotate(prev_ready=new.prev_ready)
                    with telemetry.span("serve/dispatch", cat="serve",
                                        host_operands=host_operands,
                                        device_args=device_args):
                        out = prog.step(self._kv, *step_args[:5], table,
                                        self._prev_tok, *step_args[5:])
                        # what the step handed back, and nothing kept
                        # elsewhere, is what the next step is fed
                        self._prev_tok = new.next_tok = out[0]
                        self._kv = out[2]
                        if len(out) > 3:
                            new.expert_counts = out[3]
                        for arr in (out[0], new.expert_counts):
                            start_copy = getattr(arr, "copy_to_host_async",
                                                 None)
                            if start_copy is not None:
                                start_copy()
                    new.guards = guards.pop_all()
                self._flight = new
                next_np = self._fetch(flight)
                if flight is not None and flight.expert_counts is not None:
                    # of the step just fetched, one behind this span's own
                    step_span.annotate(
                        expert_rows=int(flight.expert_counts[0]),
                        experts_touched=int(flight.expert_counts[1]))
        except Exception as e:
            self._step_failed(e, (flight, new))
            return
        self._take_in(flight, next_np)

    def _build_one_token(self, active: List[int], c: DecodeConfig):
        """The one-token step's arrays, one row a slot: ``((tokens,
        positions, seq_lens, phys, off), takers, {})`` (a row attends its
        slot's one context, which the span's ``attended`` has).  Positions
        advance here, at dispatch.  A slot's last prompt token is counted as
        the decode step it is (its taker's prefilled rows are 0)."""
        S = c.max_seqs
        tokens = np.zeros(S, np.int32)
        positions = np.zeros(S, np.int32)
        seq_lens = np.zeros(S, np.int32)
        phys = np.zeros(S, np.int32)      # inactive -> trash page 0
        off = np.zeros(S, np.int32)
        takers = []
        for i in active:
            slot = self._slots[i]
            req = slot.req
            # past the prompt the token is the last step's, on the
            # device: -1 takes prev_tok[i]
            tokens[i] = (req.prompt[slot.pos]
                         if slot.pos < req.n_prompt else -1)
            positions[i] = slot.pos
            seq_lens[i] = slot.pos + 1
            phys[i] = slot.pages[slot.pos // c.page_size]
            off[i] = slot.pos % c.page_size
            slot.pos += 1
            takes = slot.pos >= req.n_prompt
            last = takes and (
                slot.pos - req.n_prompt + 1 >= req.max_new
                or slot.pos >= c.max_seq_len)
            takers.append((i, req, takes, last, int(not takes)))
        return (tokens, positions, seq_lens, phys, off), takers, {}

    def _build_rows(self, active: List[int], c: DecodeConfig):
        """The many-token step's arrays under its fixed budget of rows:
        ``((tokens, positions, seq_lens, phys, off, row_slot, out_row),
        takers, the step span's attention counts)``: ``attn_pairs``, the
        positions attended summed over the rows (a chunk row attends up to
        its own position), of them ``chunk_pairs`` the chunk rows', and
        ``chunk_attended``, the contexts the chunk's slots hold after it
        (what an expanded-form prefill would up-project), and where the
        program carries state a slot (``carried_taps``), ``state_rows``:
        the rows that read positions before their own from that state and
        not from a row of the same step.  Row i < S is slot i's own
        (a decoding slot's token, -1: ``prev_tok[i]``); the chunk's rows go
        to the slots that still hold prompt, oldest admission first, each
        slot's rows starting a block of the program's ``chunk_block``; what
        the budget does not reach waits for the next step.  Positions advance
        here, at dispatch; a slot's last prompt row yields its first token;
        every prompt row taken counts as prefilled."""
        S, C, page = c.max_seqs, c.prefill_tokens_per_step, c.page_size
        block = self._program.chunk_block
        R = S + C
        tokens = np.zeros(R, np.int32)
        positions = np.full(R, -1, np.int32)     # a dead row
        phys = np.zeros(R, np.int32)             # ... on the trash page
        off = np.zeros(R, np.int32)
        row_slot = np.zeros(R, np.int32)
        row_slot[:S] = np.arange(S)
        seq_lens = np.zeros(S, np.int32)
        out_row = np.arange(S, dtype=np.int32)
        takers = []
        chunk_pairs = chunk_attended = state_rows = 0
        taps = getattr(self._program, "carried_taps", 0)
        in_prompt = []
        for i in active:
            slot = self._slots[i]
            req = slot.req
            if slot.pos < req.n_prompt:
                in_prompt.append(i)
                continue
            tokens[i] = -1
            positions[i] = slot.pos
            state_rows += slot.pos > 0
            seq_lens[i] = slot.pos + 1
            phys[i] = slot.pages[slot.pos // page]
            off[i] = slot.pos % page
            slot.pos += 1
            last = (slot.pos - req.n_prompt + 1 >= req.max_new
                    or slot.pos >= c.max_seq_len)
            takers.append((i, req, True, last, 0))
        decode_pairs = int(seq_lens.sum())
        at = 0                                  # chunk rows given out
        for i in sorted(in_prompt, key=lambda j: self._slots[j].req.seq):
            if at >= C:
                break
            slot = self._slots[i]
            req = slot.req
            n = min(req.n_prompt - slot.pos, C - at)
            first = S + at
            rows = slice(first, first + n)
            pos = slot.pos + np.arange(n, dtype=np.int32)
            tokens[rows] = req.prompt[slot.pos:slot.pos + n]
            positions[rows] = pos
            phys[rows] = np.asarray(slot.pages, np.int32)[pos // page]
            off[rows] = pos % page
            whole = -(-n // block) * block      # its blocks, the last padded
            row_slot[first:first + whole] = i
            if slot.pos > 0:
                state_rows += min(n, taps)
            at += whole
            slot.pos += n
            seq_lens[i] = slot.pos
            chunk_pairs += int(pos.sum()) + n
            chunk_attended += slot.pos
            takes = slot.pos >= req.n_prompt
            if takes:
                out_row[i] = first + n - 1
            last = takes and (req.max_new <= 1
                              or slot.pos >= c.max_seq_len)
            takers.append((i, req, takes, last, n))
        counts = {"attn_pairs": decode_pairs + chunk_pairs,
                  "chunk_pairs": chunk_pairs,
                  "chunk_attended": chunk_attended}
        if taps:
            counts["state_rows"] = state_rows
        return ((tokens, positions, seq_lens, phys, off, row_slot, out_row),
                takers, counts)

    def _drain(self):
        """Take in the step in flight with none dispatched behind it."""
        flight, self._flight = self._flight, None
        try:
            next_np = self._fetch(flight)
        except Exception as e:
            self._step_failed(e, (flight,))
            return
        self._take_in(flight, next_np)

    @staticmethod
    def _fetch(flight: Optional[_InFlight]) -> Optional[np.ndarray]:
        """``serve/fetch``: wait for a step's tokens (and what its expert
        layers counted); its watchdog watch and OOM guard, open since its
        dispatch, close here (on an error, with it).  The span says which
        step (``batch``) and whether it had already ended when the host came
        for it (``ready``: 1 the host was late, 0 the fetch is a wait and
        the device set this step's pace).  With nothing in flight (the
        iteration that starts a pipeline) the span is empty."""
        attrs = {}
        if flight is not None:
            flight.ready = int(_is_ready(flight.next_tok))
            attrs = {"batch": flight.seq, "ready": flight.ready}
        with telemetry.span("serve/fetch", cat="serve", **attrs):
            if flight is None:
                return None
            with flight.guards:
                if flight.expert_counts is not None:
                    flight.expert_counts = np.asarray(flight.expert_counts)
                return np.asarray(flight.next_tok)

    def _step_failed(self, e: BaseException, records):
        """A dispatch or a fetch raised.  The pool was DONATED into a
        step that died: state is unknown, so fail every running sequence
        (typed), drop what is in flight and start from a fresh pool —
        degraded, never wrong."""
        self._breaker.record_failure()
        with self._lock:
            self._counters["exec_failures"] += 1
        telemetry.count("serve.exec_failures")
        err = ExecFailed("decode step failed: %r" % (e,))
        for req in self._abandon(records):
            self._settle(req, DeadlineExceeded(
                "deadline passed while the step was failing")
                if req.expired() else err)
        self._kv = self._program.fresh_cache()

    def _abandon(self, records) -> List[DecodeRequest]:
        """Free every slot and drop ``records`` (steps dispatched, not
        taken in).  Returns the requests this leaves unsettled: the
        slots', and those that gave up their slot at dispatch and wait
        for a last token that will not come."""
        self._flight = self._prev_tok = None
        left = []
        for i in self._active():
            left.append(self._slots[i].req)
            self._release_slot(i)
        for rec in records:
            if rec is not None:
                if rec.guards is not None:
                    rec.guards.close()
                left.extend(t[1] for t in rec.takers)
        # a request may sit in a slot and in both records
        return [r for r in dict.fromkeys(left) if not r.done]

    def _take_in(self, flight: Optional[_InFlight], next_np):
        """``serve/retire``: a fetched step's tokens go to the requests
        it ran for.  Counts are taken here and not at dispatch, so that a
        token is never counted before it exists.  With no step fetched
        (the iteration that starts a pipeline) the span is empty."""
        with telemetry.span("serve/retire", cat="serve") as rsp:
            rsp.annotate(**({"retired": 0, "decoded": 0} if flight is None
                            else self._count_and_settle(flight, next_np)))

    def _count_and_settle(self, flight: _InFlight,
                          next_np: np.ndarray) -> dict:
        """Returns the ``serve/retire`` span's attrs: requests ``retired``,
        tokens ``decoded`` and, where the step stalled, ``stalled_ms``."""
        c = self._program.config
        self._breaker.record_success()
        # what a step costs a caller: from the last fetch's return to
        # this one's while the pipeline is full, from its own dispatch
        # when it started one
        t_fetched = time.perf_counter()
        step_time = t_fetched - max(flight.t_dispatch, self._t_fetched)
        self._t_fetched = t_fetched
        n_prefill = n_decode = 0
        ended = []
        now = time.monotonic()
        for i, req, takes, last, rows in flight.takers:
            if req.done:
                # swept, evicted, cancelled or ended on eos while this
                # step ran for it: no token, no count, no late OK
                continue
            n_prefill += rows
            if not takes:
                continue
            n_decode += 1
            tok = int(next_np[i])
            req.generated.append(tok)
            if req.token_times:
                self._itl_hist.observe(now - req.token_times[-1])
            else:
                self._ttft_hist.observe(now - req.enqueued_at)
            req.token_times.append(now)
            if last or (c.eos_id is not None and tok == c.eos_id):
                ended.append((i, req))
        with self._lock:
            # a completion the host learned of late (or a step that hung):
            # far outside what steps have been taking
            stalled = self._exec_ewma > 0.0 and step_time > max(
                5.0 * self._exec_ewma, _STALL_FLOOR_S)
            self._exec_ewma = (step_time if self._exec_ewma == 0.0 else
                               0.8 * self._exec_ewma + 0.2 * step_time)
            self._counters["steps"] += 1
            self._counters["steps_overlapped"] += flight.overlapped
            self._counters["steps_starved"] += flight.prev_ready
            self._counters["fetches_waited"] += 1 - flight.ready
            if stalled:
                self._counters["stalls"] += 1
                self._counters["stall_seconds"] += step_time
            self._counters["tokens_prefilled"] += n_prefill
            self._counters["tokens_decoded"] += n_decode
            self._counters["contexts_attended"] += flight.attended
            self._counters["pages_attended"] += flight.pages
            self._counters["host_operands"] += flight.host_operands
        # counted first, delivered second: a caller that has its answer
        # finds its tokens in the counts
        for i, req in ended:
            slot = self._slots[i]
            if slot is not None and slot.req is req:
                # ended on eos: the step dispatched behind this one ran
                # for it too, and its token will be dropped
                self._release_slot(i)
            self._settle(req)
        self._exec_hist.observe(step_time)
        self._occ_hist.observe(len(flight.takers) / float(c.max_seqs))
        telemetry.count("decode.tokens", float(n_decode), kind="decode")
        if n_prefill:
            telemetry.count("decode.tokens", float(n_prefill),
                            kind="prefill")
        telemetry.window_tick()
        telemetry.memory.note_step(flight.seq)
        attrs = {"retired": len(ended), "decoded": n_decode}
        if stalled:
            attrs["stalled_ms"] = round(1e3 * step_time, 3)
        return attrs

    # -- swap / stats --------------------------------------------------------
    def _validate_swap(self, source, canary_inputs=None):
        new = super()._validate_swap(source, canary_inputs)
        if not isinstance(new, DecodeProgram):
            with self._lock:
                self._counters["swap_failures"] += 1
            raise SwapFailed("decode engine can only swap to a "
                             "DecodeProgram, got %r"
                             % (type(new).__name__,))
        if not new.config.same_geometry(self._program.config):
            with self._lock:
                self._counters["swap_failures"] += 1
            raise SwapFailed(
                "decode geometry mismatch: %s != %s (the KV pool and "
                "running sequences carry over only across same-geometry "
                "swaps)" % (new.config.describe(),
                            self._program.config.describe()))
        new.ensure_compiled()     # the warm half: compile OUTSIDE the flip
        return new

    @staticmethod
    def _load_program(source):
        if isinstance(source, DecodeProgram):
            return source
        if hasattr(source, "forward") and hasattr(source, "input_names"):
            return source
        return DecodeProgram.load(os.fspath(source))

    def _maybe_preflight(self, prog):
        """GC307 pre-flight (MXNET_TPU_PREFLIGHT=1): prove statically
        that the step traces identically across positions and batch
        membership, report into the standard forensics dir.  Degrades to
        a log line on failure — preflight must never break serving."""
        from ..analysis import preflight as _preflight
        if not _preflight.enabled():
            return
        import logging
        try:
            rep = decode_retrace_report(prog)
            _preflight.write_report(rep, "decode")
            if rep.findings:
                logging.warning(
                    "decode preflight: %d finding(s):\n%s",
                    len(rep.findings),
                    "\n".join("  [%s] %s" % (f.rule, f.message)
                              for f in rep.findings))
        except Exception:
            logging.exception("decode preflight failed (continuing)")

    def stats(self) -> dict:
        out = super().stats()
        c = self._program.config
        occ = self._occ_hist.summary()
        with self._lock:
            counters = dict(self._counters)
        steps = max(counters.get("steps", 0), 1)
        out["decode"] = {
            "slots": c.max_seqs,
            "active_slots": len(self._active()),
            "pages_free": self._pool.available,
            "pages_total": self._pool.num_pages - 1,
            "occupancy_mean": round(occ["mean"] or 0.0, 4)
            if occ["count"] else 0.0,
            "tokens_decoded": counters.get("tokens_decoded", 0),
            "tokens_prefilled": counters.get("tokens_prefilled", 0),
            "tokens_per_step": round(
                counters.get("tokens_decoded", 0) / steps, 3),
            # running sum of the steps' seq_lens: the contexts the
            # attention read, for bytes-per-step and pool-residency maths
            "contexts_attended": counters.get("contexts_attended", 0),
            # and of the pages they lie on: over steps x max_seqs x
            # pages_per_seq, the share of the page table decode_attn walks
            "pages_attended": counters.get("pages_attended", 0),
            # steps dispatched while another was in flight: over "steps",
            # how often the loop hid the host behind the device
            "steps_overlapped": counters.get("steps_overlapped", 0),
            # what the host knew of the device: steps dispatched when the
            # one in flight had already ended (over "steps": how often the
            # device had nothing queued), fetches that found their step
            # still running (over "steps": how often the device set the
            # pace), and steps that took over 5 x the exec EWMA and 20 ms
            # (a completion learned of late, a hang) with their seconds
            "steps_starved": counters.get("steps_starved", 0),
            "fetches_waited": counters.get("fetches_waited", 0),
            "stalls": counters.get("stalls", 0),
            "stall_seconds": round(counters.get("stall_seconds", 0.0), 6),
            # host arrays a step's call handed the runtime to transfer
            # (the packed operands: 1), over the steps taken in
            "host_operands_per_step": round(
                counters.get("host_operands", 0) / steps, 3),
            # the step's state as the program laid it out, in its dtype:
            # the page pool and, of it, what lies beside the pages (a
            # convolution state a slot)
            "pool_bytes": self._program.cache_bytes,
            "state_bytes": self._program.state_bytes,
            "compiles": self._program.trace_count,
            "quantize": c.quantize,
        }
        # per-step time and the request stamps' distributions (queue wait
        # enqueued_at -> t_dispatched, time to first token, inter-token
        # gap), each from its engine histogram
        for key, hist in (("token_step_s", self._exec_hist),
                          ("queue_wait_s", self._qwait_hist),
                          ("ttft_s", self._ttft_hist),
                          ("itl_s", self._itl_hist)):
            ps = hist.percentiles((0.50, 0.99))
            if ps:
                out["decode"][key] = {"p50": round(ps[0.50], 6),
                                      "p99": round(ps[0.99], 6)}
        return out

    def close(self):
        super().close()
        for req in self._abandon((self._flight,)):
            self._settle(req, ServingError("engine closed mid-generation"))


def decode_retrace_report(prog: DecodeProgram):
    """GC307 over a DecodeProgram: trace the step at two different
    token positions / batch memberships and hand both traces to
    :func:`~mxnet_tpu.analysis.graphcheck.check_decode_retrace` — a
    program that bakes either into the trace recompiles per token."""
    from ..analysis import graphcheck
    c = prog.config
    S = c.max_seqs

    def args_at(pos, n_active):
        i32 = np.int32
        active = np.zeros(S, i32)
        active[:n_active] = 1
        positions = np.full(S, pos, i32) * active
        tokens, phys, off = (np.zeros(S, i32), np.ones(S, i32) * active,
                             positions % c.page_size)
        rows = ()
        if c.prefill_tokens_per_step:
            tokens, positions, phys, off, *rows = prog.rows_of_slots(
                tokens, positions, phys, off)
        operands = (tokens, positions, np.full(S, pos + 1, i32) * active,
                    phys, off, np.ones((S, c.pages_per_seq), i32), *rows)
        return (prog._param_leaves, prog.fresh_cache(),
                prog._operands.pack(*operands), prog._no_prev_tok)

    return graphcheck.check_decode_retrace(
        prog._packed_step_fn(count=False), args_at(1, S),
        args_at(2, max(1, S - 1)),
        target="DecodeProgram(%s)" % prog.name)
