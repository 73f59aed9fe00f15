"""Ahead-of-time compiles of each cell's program at its real size for a
described v5e (no chip needed): the memory the compiler plans and the Mosaic
kernels that must be in the program.  A compile that passes is not a chip
run.  ``python -m pytest benchmark/tests/test_aot.py -q -s`` prints the
``memory_analysis()`` figures PERF.md quotes.

The topology is described inside a fixture, never at import.
"""
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

GB = 1e9


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    import mxnet_tpu  # noqa: F401
    jax.config.update("jax_enable_compilation_cache", False)
    import mxnet_tpu.ops.pallas_kernels as pk
    pk._interpret = lambda *a: False      # lower kernels for Mosaic
    return topo.devices[0]


def _cell(name):
    from benchmark import run
    _b, _cell, files, data = run.load_cell(
        os.path.join(ROOT, "BENCHMARK.json"), name)
    return files, data["cfg"], data["traffic"]


def _mosaic_calls(text):
    return re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target='
                      r'"tpu_custom_call"', text)


def _planned_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _compile_train_step(chip, files, cfg, traffic):
    """The trainer's step as build_step_auto_layout jits it, lowered from
    shapes alone (init_state would need a device to put arrays on)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout
    from mxnet_tpu.executor import _resolve_structs
    from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    adapter = files.module("adapters", cfg["family"])
    tr = cfg["training"]
    sym = adapter.train_symbol(cfg)
    shapes, dts = adapter.train_shapes(cfg, traffic)
    spec = MeshSpec(make_mesh((1,), ("dp",), devices=[chip]))
    t = ShardedTrainer(sym, spec, lr=tr["lr"], momentum=tr["momentum"],
                       wd=tr["wd"], zero=True, param_dtype=tr["param_dtype"])
    _prog, known, _ = _resolve_structs(sym, shapes)
    t._param_shapes = {n: tuple(known[n].shape) for n in t.param_names}
    t._last_shapes = dict(shapes)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    def stored(n):
        return known[n].dtype if n.endswith(("gamma", "beta")) \
            else jnp.dtype(tr["param_dtype"])

    params = tuple(sds(known[n].shape, stored(n)) for n in t.param_names)
    mom = tuple(sds(known[n].shape, jnp.float32) for n in t.param_names)
    aux = tuple(sds(known[n].shape, jnp.float32) for n in t.prog.aux_names)
    inputs = {n: sds(shapes[n], dts.get(n, jnp.float32))
              for n in t.input_names}
    t._arm_mesh()
    rep, bat = spec.replicated(), t._batch_in_sharding()
    state = t._state_shardings()
    auto = tuple(tuple(Format(Layout.AUTO, s) for s in group)
                 for group in state)
    ins = auto + ({n: bat for n in t.input_names}, rep, (rep, rep))
    outs = auto + (rep, rep, (rep, rep))
    with t._tracing_on_mesh():
        jitted = jax.jit(t._make_step_fn(), in_shardings=ins,
                         out_shardings=outs, donate_argnums=(0, 1, 2, 5))
        return jitted.lower(params, mom, aux, inputs,
                            sds((t.prog.num_rng, 2), jnp.uint32),
                            (sds((), jnp.float32), sds((), jnp.int32))
                            ).compile()


def test_gpt2s_train_b16_compiles_with_flash_kernels(chip):
    files, cfg, traffic = _cell("gpt2s.train-b16")
    compiled = _compile_train_step(chip, files, cfg, traffic)
    calls = _mosaic_calls(compiled.as_text())
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(kernel in c for c in calls) == cfg["n_layer"], calls
    planned = _planned_bytes(compiled)
    print("gpt2s.train-b16 planned bytes: %.2f GB" % (planned / GB))
    assert 0.25 * 16 * GB < planned < 15.5 * GB


def test_resnet50_train_b128_compiles_without_mosaic(chip):
    pytest.importorskip("jax")
    try:
        files, cfg, traffic = _cell("resnet50.train-b128")
    except SystemExit:
        pytest.skip("resnet50.train-b128 is not in BENCHMARK.json")
    compiled = _compile_train_step(chip, files, cfg, traffic)
    assert not _mosaic_calls(compiled.as_text())
    planned = _planned_bytes(compiled)
    print("resnet50.train-b128 planned bytes: %.2f GB" % (planned / GB))
    # the compiler keeps only the convolutions' outputs: an eighth of the
    # chip, which stands because the device is busy over 75 % of the window
    assert 0.125 * 16 * GB < planned < 15.5 * GB


def test_gpt2s_decode_step_compiles_with_decode_attn(chip, monkeypatch):
    try:
        files, cfg, traffic = _cell("gpt2s.serve-closed32")
    except SystemExit:
        pytest.skip("gpt2s.serve-closed32 is not in BENCHMARK.json")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("MXNET_TPU_PALLAS_DECODE", "1")
    ref = files.module("refs", cfg["family"])
    adapter = files.module("adapters", cfg["family"])
    shapes = ref.param_shapes(cfg)
    # the program object needs host arrays to exist; zeros do for a compile
    weights = {k: np.zeros(s, np.float32) for k, s in shapes.items()}
    prog = adapter.decode_program(cfg, traffic, weights)
    c = prog.config
    on = SingleDeviceSharding(chip)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=on)

    S = c.max_seqs
    pool = (c.num_layers, 2, c.pool_pages(), c.heads, c.page_size,
            c.head_dim)
    i32 = jnp.int32
    compiled = jax.jit(prog._make_step_fn(count=False),
                       donate_argnums=(1,)).lower(
        {k: sds(v.shape, jnp.float32) for k, v in weights.items()},
        sds(pool, jnp.float32), sds((S,), i32), sds((S,), i32),
        sds((S,), i32), sds((S,), i32), sds((S,), i32),
        sds((S, c.pages_per_seq), i32)).compile()
    calls = _mosaic_calls(compiled.as_text())
    assert sum("decode_attn" in k for k in calls) == cfg["n_layer"], calls
    planned = _planned_bytes(compiled)
    print("gpt2s.serve-closed32 planned bytes: %.2f GB (pool %.2f GB)"
          % (planned / GB, 4 * np.prod(pool) / GB))
    assert 0.25 * 16 * GB < planned < 15.5 * GB
