"""Arithmetic shared by the per-layer readers under ``metrics/``."""
from benchmark.lib import trace


def idle_pct(facts):
    """100 * (1 - union of device-op intervals / traced window)."""
    traced = facts.get("traced")
    if not traced or not facts.get("events"):
        return None
    busy = trace.busy_seconds(facts["events"])
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / traced["window_s"])


def roofline_pct(facts, kernel, flops, bytes_):
    """The least time the chip could take for ``flops`` and ``bytes_`` (the
    larger of the two over their peaks) over the device seconds of the
    operations named ``kernel`` in the traced window."""
    if not facts.get("events"):
        return None
    seconds = trace.seconds_by_name(facts["events"], kernel)
    if not seconds:
        return None
    peaks = facts["peaks"]
    least = max(flops / peaks["flops"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
