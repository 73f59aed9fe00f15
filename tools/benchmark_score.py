#!/usr/bin/env python
"""Inference/scoring throughput (the reference's
example/image-classification/benchmark_score.py role): forward-only
ResNet-50 on resident data, one jitted program, images/sec/chip.

Usage: python tools/benchmark_score.py [batch ...]   (default 1 32 128)
Prints one JSON line per batch size.  Reference anchor: K80 resnet-50
bs32 = 109 img/s (example/image-classification/README.md:147-156).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main():
    batches = [int(a) for a in sys.argv[1:]] or [1, 32, 128]
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    iters = int(os.environ.get("BENCH_ITERS", "100"))

    import jax
    import jax.numpy as jnp
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.executor import _resolve_structs

    from mxnet_tpu.models.resnet import get_symbol
    sym = get_symbol(num_classes=1000, num_layers=50,
                     image_shape="3,224,224", dtype=dtype)

    for batch in batches:
        shapes = {"data": (batch, 3, 224, 224), "softmax_label": (batch,)}
        prog, known, _ = _resolve_structs(sym, shapes)
        key = jax.random.PRNGKey(0)
        rngs = iter(jax.random.split(key, len(prog.arg_names) + 1))
        wdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32

        def arg_dtype(n):
            # trainer parity: norm affines stay f32, weights follow dtype
            if (n.endswith(("gamma", "beta")) or n == "data"
                    or n.endswith("label")):
                return jnp.float32
            return wdt

        args = tuple(
            (jax.random.normal(next(rngs), known[n].shape, jnp.float32)
             * 0.05).astype(arg_dtype(n))
            for n in prog.arg_names)
        aux = tuple(
            (jnp.zeros if "mean" in n else jnp.ones)(known[n].shape,
                                                     jnp.float32)
            for n in prog.aux_names)
        keys = jnp.zeros((prog.num_rng, 2), jnp.uint32)

        @jax.jit
        def score(args, aux, keys):
            outs, _ = prog.evaluate(args, aux, keys, False)
            return outs[0]

        out = score(args, aux, keys)
        jax.block_until_ready(out)             # compile + sync
        t0 = time.perf_counter()
        for _ in range(iters):
            out = score(args, aux, keys)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        print(json.dumps({
            "metric": "resnet50_score_img_per_sec",
            "value": round(batch * iters / dt, 2),
            "unit": "images/sec (bs%d, %s, forward only)" % (batch, dtype),
            "vs_k80_bs32_109": round(batch * iters / dt / 109.0, 2)
            if batch == 32 else None,
        }), flush=True)


if __name__ == "__main__":
    main()
