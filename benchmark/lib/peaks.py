"""The one table of published chip peaks, keyed by jax's ``device_kind``.

Copied from ``mxnet_tpu/analysis/costmodel.CHIP_PEAKS`` so that a later change
to the program cannot move the yardstick.  Source: Google Cloud documentation,
"TPU v5e" (197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip).  A kind that
is not in the table is an error, never a default.
"""

CHIP_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def chip_peaks(device_kind):
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peaks for device kind %r; add it to "
                       "benchmark/lib/peaks.py with its source"
                       % (device_kind,)) from None
