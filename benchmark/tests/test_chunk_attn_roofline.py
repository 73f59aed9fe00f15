"""``chunk_attn_roofline`` on a tiny hand-made trace: the counts come from the
``serve/decode_step`` spans that began in the window, the seconds from the
``chunk_attn`` operations; a run without the spans' chunk counts or without
the kernel (a one-token step, the parent's) reads as nothing."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import harness, program_trace  # noqa: E402

CFG = {"n_layer": 12, "n_embd": 768, "serving": {"dtype": "float32"}}
PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
TPU = "/device:TPU:0"
STEP = "serve/decode_step"


def _read(facts):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "metrics", "chunk_attn_roofline.py")).read(facts)


def _spans():
    """Three steps: one before the window (left out), one whose chunk is a
    new prompt's first 96 rows, one that carries on a prompt 200 rows in."""
    return [
        ("python#1", STEP, 10.0, 5.0, {"n_prefill": 96, "chunk_attended": 96,
                                       "chunk_pairs": 4656, "attended": 9}),
        ("python#1", STEP, 100.0, 5.0, {"n_prefill": 96,
                                        "chunk_attended": 96,
                                        "chunk_pairs": 96 * 97 // 2,
                                        "attended": 500}),
        ("python#1", STEP, 200.0, 5.0, {"n_prefill": 96,
                                        "chunk_attended": 296,
                                        "chunk_pairs": 96 * 200 + 96 * 97 // 2,
                                        "attended": 700}),
        ("python#1", "serve/fetch", 210.0, 1.0, {"chunk_attended": 999}),
    ]


def _facts(chunk_ns):
    events = [(TPU, "XLA Ops", "chunk_attn.3", 110.0, chunk_ns),
              (TPU, "XLA Ops", "decode_attn.2", 120.0, 5e5)]
    return {"events": events, "peaks": PEAKS, "cfg": CFG}


def test_bytes_held_before_the_chunk_over_the_kernels_seconds(monkeypatch):
    monkeypatch.setattr(program_trace, "of_run",
                        lambda facts: (_spans(), [], (50.0, 300.0)))
    # 200 positions held before the chunk (0 + 200), 12 layers, K and V,
    # 768 floats of 4 B: 14.75 MB; pairs 4,656 + 23,856 at 4 x 768 x 12
    # FLOPs: 1.05 GFLOP.  Bytes bound it: 18.0 us at 819 GB/s
    held_bytes = 200 * 12 * 2 * 768 * 4
    flops = (4656 + 96 * 200 + 4656) * 12 * 4 * 768
    least = max(held_bytes / 819e9, flops / 197e12)
    assert least == held_bytes / 819e9
    value = _read(_facts(100e3))            # 100 us of chunk_attn
    assert value == pytest.approx(100.0 * least / 100e-6)
    assert 0 < value <= 100


def test_nothing_to_read_reads_as_nothing(monkeypatch, tmp_path):
    # untraced, or traced with nothing on disk
    assert _read({"steps": 3}) is None
    monkeypatch.setattr(program_trace, "TRACE_DIR", str(tmp_path / "none"))
    assert _read(_facts(1e5)) is None
    # a one-token step's spans: no chunk counts
    plain = [s[:4] + ({"n_prefill": 3, "attended": 40},) for s in _spans()]
    monkeypatch.setattr(program_trace, "of_run",
                        lambda facts: (plain, [], (50.0, 300.0)))
    assert _read(_facts(1e5)) is None
    # the counts, but no chunk_attn ran
    monkeypatch.setattr(program_trace, "of_run",
                        lambda facts: (_spans(), [], (50.0, 300.0)))
    facts = _facts(1e5)
    facts["events"] = facts["events"][1:]
    assert _read(facts) is None
