"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
Apache MXNet (reference: pgplus1628/mxnet v1.1.0-dev), built from scratch on
JAX/XLA.  See SURVEY.md at the repo root for the layer-by-layer mapping.

Usage mirrors the reference:

    import mxnet_tpu as mx
    a = mx.nd.ones((2, 3), ctx=mx.tpu())
    net = mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=10)
    mod = mx.mod.Module(net, context=mx.tpu())
"""
# __version__ comes from libinfo (imported below); the C ABI serves the
# paired integer form (capi.py VERSION = 10100 -> MXGetVersion)

# float64 NDArrays are first-class in the reference; enable the x64 lane.
# All internal creation paths pass explicit dtypes, so float32 stays the
# default everywhere (weak-typed python scalars never promote inputs).
import jax as _jax
_jax.config.update("jax_enable_x64", True)
# float32 matmuls must BE float32 (reference parity): this build's default
# matmul precision truncates f32 to bf16 passes even on CPU.  bfloat16
# workloads are unaffected — bf16 inputs hit the MXU natively either way.
_jax.config.update("jax_default_matmul_precision", "highest")
# jax's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR says
# (jax reads that itself), else one fixed directory inside the checkout —
# see compile/paths.py.  Every program is kept, however fast it compiled:
# a cold process on the chip pays seconds for each one it lacks.
import os as _os
from .compile import paths as _paths
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir", _paths.jax_cache_dir())
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

from .base import MXNetError
from .attribute import AttrScope
from .context import (Context, cpu, cpu_pinned, current_context, gpu,
                      num_gpus, num_tpus, tpu)
from . import (ops, operator, ndarray, autograd, random, rtc, engine,
               libinfo, log)
from .libinfo import __version__
from .rng import seed
from . import (name, symbol, executor, initializer, optimizer, metric,
               lr_scheduler, callback, io, recordio, kvstore, model,
               module, monitor, profiler, test_utils, visualization)
from .executor import Executor, set_backward_mirror, backward_mirror_policy
from .symbol import Symbol
from .optimizer import Optimizer
from .kvstore import KVStore
from .model import FeedForward
from .monitor import Monitor
from .executor_manager import DataParallelExecutorManager
from . import parallel, gluon, image, rnn, contrib
from . import resilience
from . import serving
from . import telemetry
from . import compile
from . import sparse

# reference-style short aliases (mx.nd, mx.sym, mx.mod, ...)
nd = ndarray
sym = symbol
init = initializer
kv = kvstore
mod = module
mon = monitor
viz = visualization
