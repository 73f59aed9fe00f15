"""srclint fixture for the library-only rule SL106: a shard_map entry
point that executes collectives with no watchdog arming.  Parsed with
``in_library=True`` by tests/test_analysis.py; never imported."""
import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P


def unarmed_entry(fn, mesh, x):                           # SL106
    mapped = shard_map(fn, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    return jax.jit(mapped)(x)


def armed_entry(fn, mesh, x):
    from mxnet_tpu.resilience import watchdog as _wd
    mapped = shard_map(fn, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    with _wd.watch("fixture.armed_entry", kind="collective"):
        return jax.jit(mapped)(x)
