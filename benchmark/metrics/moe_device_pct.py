"""Share of the device's busy seconds under the expert operator's scopes
(``mx._contrib_moe_ffn.<node>``: routing, gather, grouped products, shared
expert, combine; forward and backward)."""
from benchmark.lib import moe_scopes


def read(facts):
    found = moe_scopes.of_run(facts)
    if found is None:
        return None
    parts, total = found
    return 100.0 * sum(parts.values()) / total
