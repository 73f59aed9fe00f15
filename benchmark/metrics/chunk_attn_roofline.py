"""The ``chunk_attn`` calls of the traced steps (a many-token GPT-2 step's
prompt rows): the larger of two least times over the kernel's device seconds.
Bytes: the keys and values of the positions the chunk's slots held BEFORE the
chunk (``chunk_attended`` - ``n_prefill`` of the ``serve/decode_step`` spans:
the contexts after the chunk less its own rows), every layer, in the serving
dtype, over the HBM peak.  FLOPs: ``chunk_pairs`` (positions the chunk's rows
attend, their own included) x 4 x ``n_embd`` a layer (the scores and the
weighted sum), over the peak.  Neither can be avoided by any kernel, so the
share cannot pass 100%.  A program whose steps carry no chunk counts (one
token a slot a step) or run no ``chunk_attn`` reads as nothing."""
import numpy as np

from benchmark.lib import decode_step_trace, program_trace, readers


def read(facts):
    steps = decode_step_trace.step_counts(
        program_trace.of_run(facts),
        ("chunk_attended", "n_prefill", "chunk_pairs"))
    if steps is None:
        return None
    cfg = facts["cfg"]
    itemsize = np.dtype(cfg.get("serving", {}).get("dtype",
                                                   "float32")).itemsize
    per_layer = cfg["n_layer"] * cfg["n_embd"]
    held = sum(after - rows for after, rows, _pairs in steps)
    pairs = sum(pairs for _after, _rows, pairs in steps)
    return readers.roofline_pct(facts, "chunk_attn", 4 * per_layer * pairs,
                                2 * per_layer * itemsize * held)
