"""What the per-layer readers of a many-token serving cell take out of the
traced run: the ``serve/decode_step`` spans' own counts (``attended``,
``attn_pairs``, ``chunk_pairs``, ``chunk_attended``, ``expert_rows``,
``experts_touched``) and the device seconds
under ``mx.decode.moe`` and its parts.  A program without the span attribute
or the scope (every commit before PR 35) leaves the readers nothing: they
return None and the metric is left out of the line."""
import re

from benchmark.lib import program_trace
from benchmark.lib.moe_scopes import PART, REST

OPERATOR = re.compile(r"mx\.decode\.moe(?![\w.])")


def step_counts(run, names):
    """[[the attributes ``names`` of one ``serve/decode_step`` span], ...]
    over the spans that began in the window and carry them all, or None
    where none does."""
    if run is None:
        return None
    spans, _ops, (w0, w1) = run
    steps = [[float(attrs[key]) for key in names]
             for _thread, name, start, _dur, attrs in spans
             if name == "serve/decode_step" and w0 <= start < w1
             and all(key in attrs for key in names)]
    return steps or None


def step_sums(run, names):
    """{name: sum of the attribute over those spans}, or None."""
    steps = step_counts(run, names)
    if steps is None:
        return None
    return dict(zip(names, map(sum, zip(*steps))))


def moe_seconds(run):
    """({part: device seconds under ``mx.decode.moe``}, all device seconds
    in the window), or None where the trace holds no such scope."""
    if run is None:
        return None
    parts, total = {}, 0.0
    for _plane, _op, path, _start, dur in run[1]:
        total += dur / 1e9
        found = OPERATOR.search(path)
        if found:
            inner = PART.findall(path[found.end():])
            key = inner[-1] if inner else REST
            parts[key] = parts.get(key, 0.0) + dur / 1e9
    return (parts, total) if parts and total > 0 else None
