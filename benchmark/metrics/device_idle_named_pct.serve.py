"""Share of the first device's idle seconds in the traced window whose gap's
midpoint lies inside one of the program's own spans (``serve/*``, innermost):
how much of ``device_idle_pct.serve`` the trace can put a name to."""
from benchmark.lib import program_trace


def read(facts):
    return program_trace.idle_named_pct(program_trace.of_run(facts))
