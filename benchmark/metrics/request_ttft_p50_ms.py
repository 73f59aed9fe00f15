"""Median time to the first token of the requests that settled ``ok`` in the
traced window: ``ttft_us`` of the engine's ``serve/request_done`` spans."""
from benchmark.lib import step_pipeline


def read(facts):
    return step_pipeline.request_ttft_p50_ms(step_pipeline.of_run(facts))
