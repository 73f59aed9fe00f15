"""Share of the device's busy seconds under the decode step's
``mx.decode.kv_write`` scope: the two scatters of a token's K and V into the
page pool, and whatever copies of the pool XLA puts under their name."""
from benchmark.lib import program_trace


def read(facts):
    return program_trace.scope_share_pct(
        program_trace.of_run(facts),
        lambda scope: scope == "mx.decode.kv_write")
