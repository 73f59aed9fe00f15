from benchmark.lib import program_trace


def read(facts):
    return program_trace.scoped_pct(program_trace.of_run(facts))
