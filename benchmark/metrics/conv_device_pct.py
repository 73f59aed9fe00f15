"""Share of the device's busy seconds under the decode step's
``mx.decode.conv`` scope: the short convolutions' input projections, the
segmented convolution with its state's read and write (``shift``) and the
output projections.  A program without the scope reads as nothing."""
from benchmark.lib import program_trace


def read(facts):
    return program_trace.scope_share_pct(
        program_trace.of_run(facts), lambda scope: scope == "mx.decode.conv")
