"""Compile-time plane: persistent executable cache + AOT warm standby.

ROADMAP item 5 ("recovery without recompilation"): the ``compile/*``
span family and ``compile.seconds`` accounting landed with the tracing
PR; this package adds the machinery that makes them flat lines during
recovery —

* :mod:`.cache` — a persisted, CRC-validated cache of serialized XLA
  executables (the resilience container format) keyed by program
  fingerprint × device signature; corrupt entries quarantine and fall
  back to a fresh compile, never a wrong executable;
* :mod:`.standby` — a background pre-compiler that, while training at
  world N, compiles the N−1 / grow-back generation step programs into
  the cache so an elastic resize resumes with zero in-drill
  compilation;
* :mod:`.paths` — the shared ``<checkout>/.cache`` / ``MXNET_TPU_*_
  CACHE`` location convention (also used by ``ops/autotune.py``);
* :mod:`.treedefs` — the pickle-free pytree codec cached entries use
  for their call signatures.

See docs/robustness.md ("Recovery without recompilation") for the knob
table and semantics.
"""
from . import paths
from .treedefs import UnsupportedTreedef, obj_to_treedef, treedef_to_obj
from .cache import (arm, cache_dir, cache_stats, cached_compile, clear,
                    device_signature, disarm, donation_safe, enabled,
                    outside_jax_cache, program_fingerprint)
from .standby import StandbyCompiler, trainer_standby_jobs

__all__ = [
    "paths", "UnsupportedTreedef", "obj_to_treedef", "treedef_to_obj",
    "arm", "cache_dir", "cache_stats", "cached_compile", "clear",
    "device_signature", "disarm", "donation_safe", "enabled",
    "outside_jax_cache", "program_fingerprint",
    "StandbyCompiler", "trainer_standby_jobs",
]
