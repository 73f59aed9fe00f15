"""Share of the device's busy seconds under the expert operator's ``route``
(router product, top-k, sort), ``dispatch`` (gather) and ``combine``
(weighted scatter-add) scopes: what routing costs beside the products."""
from benchmark.lib import moe_scopes


def read(facts):
    found = moe_scopes.of_run(facts)
    if found is None:
        return None
    parts, total = found
    hit = sum(parts.get(k, 0.0) for k in ("route", "dispatch", "combine"))
    return 100.0 * hit / total if hit > 0 else None
