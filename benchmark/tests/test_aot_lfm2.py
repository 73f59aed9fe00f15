"""Ahead-of-time compile of ``lfm2-8b-a1b.serve-closed128-4k``'s decode step
at its real size for a described v5e (no chip needed; outside tier-1, like
``test_aot.py``, whose helpers and fixture it uses): the memory the compiler
plans for the step's parameters, K/V pool, convolution state and
temporaries, the pool's three Mosaic kernels once a step per attention layer
with a query group a key/value head, and the experts' grouped products.  The
step is built from shapes alone: no weight is made.  A compile that passes is
not a chip run.  ``python -m pytest benchmark/tests/test_aot_lfm2.py -q -s``
prints the figures PERF.md quotes.
"""
from benchmark.tests.test_aot import (  # noqa: F401  (chip: the fixture)
    GB, _cell, _mosaic_calls, _planned_bytes, chip)

CELL = "lfm2-8b-a1b.serve-closed128-4k"


def test_lfm2_decode_step_compiles_with_grouped_kernels(chip):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from mxnet_tpu.models import lfm2_moe
    from mxnet_tpu.serving.decode import DecodeConfig, HybridDecodeProgram
    _files, cfg, traffic = _cell(CELL)
    S, C = traffic["slots"], traffic["prefill_tokens_per_step"]
    R = S + C
    dc = DecodeConfig(cfg["vocab_size"], cfg["num_hidden_layers"],
                      cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["n_positions"], page_size=traffic["page_size"],
                      max_seqs=S, family="lfm2_moe",
                      dtype=cfg["serving"]["dtype"],
                      prefill_tokens_per_step=C,
                      model=lfm2_moe.model_of(cfg),
                      kv_heads=cfg["num_key_value_heads"])
    model, L = dc.model, dc.num_layers
    decoder = lfm2_moe.Decoder(model, num_layers=L, vocab_size=dc.vocab_size,
                               slots=S, chunk_rows=C, dtype=dc.dtype)
    on = SingleDeviceSharding(chip)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=on)

    bf16 = jnp.dtype(dc.dtype)
    params = {k: sds(s, jnp.float32 if lfm2_moe.is_float32_param(k)
                     else bf16)
              for k, s in lfm2_moe.param_shapes(model, L,
                                                dc.vocab_size).items()}
    pool_shape = HybridDecodeProgram.pool_shape_of(dc)
    state = {"kv": sds(pool_shape, bf16),
             "conv": sds(lfm2_moe.state_shape(model, L, S), bf16)}
    assert pool_shape == (3, 2, 1 + S * 64, 8, 32, 128)

    def step(p, st, *rows):
        return decoder.step(p, st, *rows, use_pallas=True)

    lowered = jax.jit(step, donate_argnums=(1,)).lower(
        params, state, sds((R,)), sds((R,)), sds((S,)), sds((R,)),
        sds((R,)), sds((S, dc.pages_per_seq)), sds((S,)), sds((R,)),
        sds((S,)))
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    for kernel in ("kv_write", "decode_attn", "chunk_attn"):
        assert sum(kernel in c for c in calls) == 3, calls
    grouped = [c for c in calls if "gmm" in c]
    assert len(grouped) >= 3 * (L - model["num_dense_layers"]), calls
    ma = compiled.memory_analysis()
    planned = _planned_bytes(compiled)
    print("%s planned bytes: %.2f GB (arguments %.2f GB, temporaries %.2f "
          "GB)" % (CELL, planned / GB, ma.argument_size_in_bytes / GB,
                   ma.temp_size_in_bytes / GB))
    # the state is donated: written where it lies, never copied whole
    assert ma.alias_size_in_bytes >= 2 * (
        int(jax.numpy.prod(jnp.asarray(pool_shape))))
    assert 11 * GB < planned < 15.5 * GB
