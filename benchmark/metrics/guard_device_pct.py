"""Share of the device's busy seconds under the trainer's ``mx.guard`` scope:
the non-finite check over the loss and every gradient, and the selects that
keep the old state when it trips."""
from benchmark.lib import program_trace


def read(facts):
    return program_trace.scope_share_pct(program_trace.of_run(facts),
                                         lambda scope: scope == "mx.guard")
