"""Host milliseconds a decode step that the engine spends outside its wait for
the device: the program's own spans ``serve/admit`` + ``serve/build`` +
``serve/dispatch`` + ``serve/retire`` of the iterations that began in the
traced window, over its ``serve/decode_step`` spans."""
from benchmark.lib import program_trace


def read(facts):
    return program_trace.engine_host_ms(program_trace.of_run(facts))
