"""The held experts' three grouped products in the traced steps: the larger
of their FLOPs at the held picks (``expert_rows``) and the bytes of the
experts touched (``experts_touched``), both from the ``serve/decode_step``
spans, over the device seconds under ``mx.decode.moe`` > ``experts``."""
from benchmark.lib import decode_step_trace, program_trace
from benchmark.lib import sarvam_counts as counts


def read(facts):
    run = program_trace.of_run(facts)
    sums = decode_step_trace.step_sums(run, ("expert_rows",
                                             "experts_touched"))
    found = decode_step_trace.moe_seconds(run)
    if sums is None or found is None or not found[0].get("experts"):
        return None
    flops, bytes_ = counts.held_experts_least(
        facts["cfg"], sums["expert_rows"], sums["experts_touched"])
    peaks = facts["peaks"]
    least = max(flops / peaks["flops"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / found[0]["experts"]
