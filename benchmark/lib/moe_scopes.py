"""Device seconds under the expert operator's scopes, from the traced run:
``mx._contrib_moe_ffn.<node>`` (the executor's) and, inside it, ``route``,
``dispatch``, ``experts``, ``shared``, ``combine`` (``parallel/moe.py``).  A
program without the operator (every commit before PR 33) has no such scope:
the readers then return None and the metric is left out of the line."""
import re

from benchmark.lib import program_trace

OPERATOR = re.compile(r"mx\._contrib_moe_ffn\.[^/():]+")
PART = re.compile(r"(?<![\w.])(route|dispatch|experts|shared|combine)(?![\w.])")
REST = "(rest)"


def seconds_by_part(run):
    """({part: device seconds under the operator}, all device seconds in
    the window), or None where the run has no trace or no such operator.
    A fusion counts under its root's scope."""
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


def of_run(facts):
    return seconds_by_part(program_trace.of_run(facts))
