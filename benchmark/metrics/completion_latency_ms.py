"""The time after which the host learns that a step has ended: over the traced
window's steps whose fetch found them still running (``ready`` = 0), the
median of ``serve/fetch`` end - the device run's end, with the device's clock
set so that the window's earliest run start falls on its ``serve/dispatch``'s
start (``lib/step_pipeline.py``): free of the offset between the trace's two
clocks, and an upper bound (over by the window's fastest launch)."""
from benchmark.lib import step_pipeline


def read(facts):
    return step_pipeline.completion_latency_ms(step_pipeline.of_run(facts))
