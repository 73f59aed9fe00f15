"""Share of the device's busy seconds that carry a name the program chose: an
``mx.*`` scope (``jax.named_scope`` in the executor, the trainer and the decode
step) or a Pallas kernel's own name.  What is left is what a breakdown by
scope cannot place."""
from benchmark.lib import program_trace


def read(facts):
    return program_trace.scoped_pct(program_trace.of_run(facts))
