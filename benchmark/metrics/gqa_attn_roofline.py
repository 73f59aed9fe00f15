"""The ``decode_attn`` and ``chunk_attn`` calls of the traced steps of a
grouped-query pool: the larger of the keys' and values' bytes of the contexts
attended (each read once, every attention layer) over the HBM peak and the
attention pairs' FLOPs over the bf16 peak (``lib/lfm2_counts.gqa_attn_least``
from the ``serve/decode_step`` spans' ``attended`` and ``attn_pairs``), over
the two kernels' device seconds together.  A program whose spans carry no
such counts or whose trace holds neither kernel reads as nothing."""
import numpy as np

from benchmark.lib import decode_step_trace, program_trace, trace
from benchmark.lib import lfm2_counts as counts


def read(facts):
    steps = decode_step_trace.step_counts(program_trace.of_run(facts),
                                          ("attended", "attn_pairs"))
    if steps is None or not facts.get("events"):
        return None
    seconds = [trace.seconds_by_name(facts["events"], name)
               for name in ("decode_attn", "chunk_attn")]
    seconds = sum(s for s in seconds if s)
    if not seconds:
        return None
    itemsize = np.dtype(facts["cfg"]["serving"]["dtype"]).itemsize
    flops, bytes_ = counts.gqa_attn_least(facts["cfg"], steps, itemsize)
    peaks = facts["peaks"]
    least = max(flops / peaks["flops"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
