"""Block-size autotuner (ops/autotune.py): cache round-trip and
persistence, measure-driven search semantics, trace-time safety of the
read path, and the flash kernel integration."""
import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu  # noqa: F401
from mxnet_tpu import telemetry
from mxnet_tpu.ops import autotune


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.delenv("MXNET_TPU_AUTOTUNE", raising=False)
    autotune.invalidate()
    telemetry.reset()
    telemetry.disarm()
    yield
    autotune.invalidate()
    telemetry.reset()


def test_defaults_without_cache():
    assert autotune.flash_blocks("fwd", 8192, 8192, 64, "bfloat16") \
        == autotune.DEFAULT_FLASH_BLOCKS["fwd"]
    assert autotune.flash_blocks("bwd", 8192, 8192, 64, "bfloat16") \
        == autotune.DEFAULT_FLASH_BLOCKS["bwd"]


def test_record_lookup_and_persistence():
    sig = ("fwd", 4096, 4096, 64, "bfloat16")
    autotune.record("flash_fwd", sig, (256, 512), 3.2, trials=6)
    assert autotune.flash_blocks("fwd", 4096, 4096, 64, "bfloat16") \
        == (256, 512)
    # a fresh process (simulated by dropping the in-memory cache) reads
    # the persisted winner back
    autotune.invalidate()
    assert autotune.flash_blocks("fwd", 4096, 4096, 64, "bfloat16") \
        == (256, 512)
    raw = json.load(open(autotune.cache_path()))
    (entry,) = raw.values()
    assert entry["config"] == [256, 512]
    assert entry["score_ms"] == pytest.approx(3.2)
    assert entry["device_kind"] == autotune.device_kind()


def test_key_discriminates_shape_dtype():
    autotune.record("flash_fwd", ("fwd", 1024, 1024, 64, "bfloat16"),
                    (512, 512), 1.0)
    assert autotune.flash_blocks("fwd", 1024, 1024, 64, "bfloat16") \
        == (512, 512)
    # different T / dtype: default again
    assert autotune.flash_blocks("fwd", 2048, 2048, 64, "bfloat16") \
        == autotune.DEFAULT_FLASH_BLOCKS["fwd"]
    assert autotune.flash_blocks("fwd", 1024, 1024, 64, "float32") \
        == autotune.DEFAULT_FLASH_BLOCKS["fwd"]


def test_autotune_disabled_returns_default_without_measuring():
    calls = []
    got = autotune.autotune("op", ("sig",), [(1,), (2,)],
                            lambda c: calls.append(c) or 1.0,
                            default=(9,))
    assert got == (9,) and calls == []


def test_autotune_measures_picks_fastest_and_caches():
    telemetry.arm()
    times = {(1,): 0.02, (2,): 0.005, (3,): 0.01}
    calls = []

    def measure(c):
        calls.append(c)
        return times[c]

    got = autotune.autotune("op", ("s1",), [(1,), (2,), (3,)], measure,
                            force=True)
    assert got == (2,) and len(calls) == 3
    # second call: pure cache hit, no measuring
    calls.clear()
    got2 = autotune.autotune("op", ("s1",), [(1,), (2,), (3,)], measure,
                             force=True)
    assert got2 == (2,) and calls == []
    # the search itself landed on the measurement plane
    assert telemetry.counter("autotune.trials").total() == 3
    assert telemetry.histogram(
        "autotune.trial_seconds").summary()["count"] == 3


def test_autotune_skips_failing_candidates():
    def measure(c):
        if c == (1,):
            raise RuntimeError("over VMEM budget")
        return 0.5

    got = autotune.autotune("op", ("s2",), [(1,), (2,)], measure,
                            force=True)
    assert got == (2,)


def test_autotune_all_fail_raises():
    """One failing candidate is data for the search; when every candidate
    fails the default cannot run either, and that is an error carrying
    the last failure — never a quiet fallback."""
    def measure(c):
        raise RuntimeError("no")

    with pytest.raises(RuntimeError, match="every candidate failed") as ei:
        autotune.autotune("op", ("s3",), [(1,), (2,)], measure,
                          default=(7,), force=True)
    assert str(ei.value.__cause__) == "no"
    assert autotune.lookup("op", ("s3",)) is None


def test_flash_candidates_respect_vmem_budget():
    cands = autotune._flash_candidates("bwd", 32768, 32768, 64)
    assert cands, "candidate set must never be empty"
    for bq, bk in cands:
        assert bq <= 32768 and bk <= 32768
    # blocks stay in the operands' dtype: in bfloat16 at D=64 the static
    # defaults and everything up to 1024 x 1024 fit
    for kind in ("fwd", "bwd"):
        small = autotune._flash_candidates(kind, 32768, 32768, 64)
        assert autotune.DEFAULT_FLASH_BLOCKS[kind] in small
        assert (1024, 1024) in small
    # a (1024, 2048) backward cell at D=256 blows the 12MB budget, and
    # float32 operands (4 bytes) blow it sooner than bfloat16
    big = autotune._flash_candidates("bwd", 32768, 32768, 256)
    assert (512, 1024) in big and (1024, 2048) not in big
    f32 = autotune._flash_candidates("bwd", 32768, 32768, 256, itemsize=4)
    assert set(f32) < set(big)
    # never a block longer than the sequence
    assert autotune._flash_candidates("fwd", 256, 512, 64) \
        == [(128, 128), (128, 256), (128, 512), (256, 128), (256, 256),
            (256, 512)]


def test_fused_attention_uses_cached_blocks(monkeypatch):
    """The kernel wrapper consults the cache at trace time: plant an
    entry and observe it win over the static default (visible through
    the clamping behavior at small T: a cached (8, 8) beats the
    static default)."""
    from mxnet_tpu.ops import pallas_kernels as pk
    seen = {}
    real = pk._flash_call

    def spy(operands, *, block_q, block_k, **kw):
        seen["blocks"] = (block_q, block_k)
        return real(operands, block_q=block_q, block_k=block_k, **kw)

    monkeypatch.setattr(pk, "_flash_call", spy)
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.normal(0, 1, (1, 32, 1, 8)).astype(np.float32))
    autotune.record("flash_fwd", ("fwd", 32, 32, 8, "float32"), (8, 8),
                    1.0)
    pk.fused_attention(q, q, q)
    assert seen["blocks"] == (8, 8)


def test_tune_flash_end_to_end_interpret(tmp_path):
    """The flash search driver runs (forced) on the interpret path and
    persists winners for both directions."""
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.normal(0, 1, (1, 16, 1, 8)).astype(np.float32))
    res = autotune.tune_flash(q, q, q, causal=True, iters=1, force=True)
    assert set(res) == {"fwd", "bwd"}
    autotune.invalidate()
    assert autotune.lookup(
        "flash_fwd", ("fwd", 16, 16, 8, "float32")) is not None
    assert autotune.lookup(
        "flash_bwd", ("bwd", 16, 16, 8, "float32")) is not None
