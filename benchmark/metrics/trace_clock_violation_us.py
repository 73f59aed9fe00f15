"""By how much the trace's host and device clocks are shown to disagree: over the
traced window's steps, ``max(0, -min(run start - serve/dispatch start),
-min(serve/fetch end - run end))``; 0 where no step contradicts one clock
(``lib/step_pipeline.py``)."""
from benchmark.lib import step_pipeline


def read(facts):
    return step_pipeline.trace_clock_violation_us(step_pipeline.of_run(facts))
