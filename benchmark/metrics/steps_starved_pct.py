"""Share of the traced window's device runs of the decode step that began more
than 50 us after the run before them ended: the device had nothing queued
(``lib/step_pipeline.py`` on libtpu's ``XLA Modules`` line)."""
from benchmark.lib import step_pipeline


def read(facts):
    return step_pipeline.steps_starved_pct(step_pipeline.of_run(facts))
