"""The ``mla_attn`` calls of the traced steps: the larger of the latent rows'
bytes of the contexts attended over the HBM peak and the attention's least
FLOPs over the bf16 peak, over the kernel's device seconds.  The least FLOPs
(``lib/sarvam_counts.mla_attn_least``, from the ``serve/decode_step`` spans'
``attended``, ``attn_pairs``, ``chunk_pairs`` and ``chunk_attended``) are a
decoding row's absorbed products and, for a step's chunk, the cheaper of the
absorbed and the expanded form: a kernel that attends a long chunk in the
absorbed form is held against what the expanded form would have cost."""
from benchmark.lib import decode_step_trace, program_trace, readers
from benchmark.lib import sarvam_counts as counts


def read(facts):
    steps = decode_step_trace.step_counts(
        program_trace.of_run(facts),
        ("attended", "attn_pairs", "chunk_pairs", "chunk_attended"))
    if steps is None:
        return None
    flops, bytes_ = counts.mla_attn_least(facts["cfg"], steps)
    return readers.roofline_pct(facts, "mla_attn", flops, bytes_)
