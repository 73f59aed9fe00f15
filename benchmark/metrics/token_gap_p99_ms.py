"""The 99th percentile of the gap between two tokens of one request in the traced
window: the intervals between the ends of consecutive ``serve/retire`` spans
that handed out tokens, each weighted by the later one's ``decoded``."""
from benchmark.lib import step_pipeline


def read(facts):
    return step_pipeline.token_gap_p99_ms(step_pipeline.of_run(facts))
