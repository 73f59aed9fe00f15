"""Share of the device's busy seconds under the executor's
``mx.BatchNorm.<node>`` scopes, forward and backward (the backward ops carry
the node's scope through ``transpose(jvp(...))``)."""
from benchmark.lib import program_trace


def read(facts):
    return program_trace.scope_share_pct(
        program_trace.of_run(facts),
        lambda scope: scope.startswith("mx.BatchNorm."))
