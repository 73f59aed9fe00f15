"""The held experts' grouped products, forward and both backward: least time
from the shapes at the expected routed rows (``moe_flops``, ``moe_bytes`` of
the adapter's ``work_per_step``; compute bounds it at these widths) over the
device seconds under the operator's ``experts`` scope in the traced steps.
The backward pass computes the first two forward products again, which the
least time does not count."""
from benchmark.lib import moe_scopes


def read(facts):
    traced = facts.get("traced")
    work = facts["work_per_step"]
    found = moe_scopes.of_run(facts)
    if not traced or found is None or "moe_flops" not in work:
        return None
    seconds = found[0].get("experts")
    if not seconds:
        return None
    peaks = facts["peaks"]
    least = traced["steps"] * max(work["moe_flops"] / peaks["flops"],
                                  work["moe_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
