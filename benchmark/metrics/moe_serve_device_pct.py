"""Share of the device's busy seconds under the decode step's
``mx.decode.moe`` scope: routing, gather, the held experts' grouped products,
the shared expert, combine."""
from benchmark.lib import decode_step_trace, program_trace


def read(facts):
    found = decode_step_trace.moe_seconds(program_trace.of_run(facts))
    if found is None:
        return None
    parts, total = found
    return 100.0 * sum(parts.values()) / total
