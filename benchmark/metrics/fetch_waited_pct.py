"""Share of the engine's ``serve/fetch`` spans in the traced window whose step had
not ended when the host came for it (``ready`` = 0, the host's own
``is_ready()``): steps in which the host was there first and the device set
the pace."""
from benchmark.lib import step_pipeline


def read(facts):
    return step_pipeline.fetch_waited_pct(step_pipeline.of_run(facts))
