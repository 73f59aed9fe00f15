"""Ahead-of-time compile of ``trinity-mini.train-b1x8k``'s step at its real
size for a described v5e (no chip needed; outside tier-1, like
``test_aot.py``, whose helpers and fixture it uses): the memory the compiler
plans, the three flash kernels with the window in every layer, and the
grouped-product kernels of the expert layers under their scope.  A compile that passes is not a chip
run.  ``python -m pytest benchmark/tests/test_aot_afmoe.py -q -s`` prints the
figures PERF.md quotes.
"""
import re

from benchmark.tests.test_aot import (  # noqa: F401  (chip: the fixture)
    GB, _cell, _compile_train_step, _mosaic_calls, _planned_bytes, chip)

CELL = "trinity-mini.train-b1x8k"


def test_trinity_mini_train_step_compiles_with_windowed_flash(chip):
    files, cfg, traffic = _cell(CELL)
    compiled = _compile_train_step(chip, files, cfg, traffic)
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    layers = cfg["num_hidden_layers"]
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(kernel in c for c in calls) == layers, calls
    # the window is a constant of the kernel's body: four of the five
    # layers carry it, and they share one body per kernel
    windowed = [l for l in text.splitlines() if "tpu_custom_call" in l
                and "flash_" in l and "mx._contrib_fused_attention" in l]
    assert len(windowed) == 3 * layers
    # the held experts' grouped products: Mosaic kernels that keep the
    # operator's scope (XLA's own ragged-dot kernel drops it)
    grouped = [l for l in text.splitlines() if "tpu_custom_call" in l
               and re.search(r"mx\._contrib_moe_ffn\.l\d_moe.*experts.*gmm", l)]
    assert len(grouped) >= 11 * (layers - cfg["num_dense_layers"]), \
        len(grouped)
    assert "ragged-dot" not in text
    planned = _planned_bytes(compiled)
    print("%s planned bytes: %.2f GB" % (CELL, planned / GB))
    assert 4 * 2**30 < planned < 15.5 * GB
