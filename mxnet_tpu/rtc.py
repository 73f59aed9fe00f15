"""Runtime custom kernels — the CudaModule/CudaKernel analog
(reference python/mxnet/rtc.py: NVRTC-compiled CUDA source, get_kernel
:112, launch :185).

TPU-native design: there is no source-string compiler to wrap — a custom
TPU kernel IS a Pallas kernel function, and Mosaic is its compiler.  So
TPUModule holds named Pallas kernel functions; get_kernel binds one to
output shapes/dtypes; launch runs it over NDArrays via pallas_call (real
Mosaic lowering on TPU, interpreter elsewhere — same policy as
ops/pallas_kernels.py).  The reference's grid_dims maps to the pallas
grid; block shapes come from BlockSpecs the caller may supply.

    def axpy(x_ref, y_ref, out_ref, *, alpha):
        out_ref[:] = x_ref[:] * alpha + y_ref[:]

    mod = rtc.TPUModule({"axpy": axpy})
    k = mod.get_kernel("axpy", out_shapes=[(8, 128)], alpha=2.0)
    (out,) = k.launch([x, y])
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Sequence

import jax
import numpy as np

from .base import MXNetError, dtype_np
from .ndarray.ndarray import NDArray

__all__ = ["TPUModule", "TPUKernel", "CudaModule"]


class TPUKernel:
    """A bound custom kernel (reference CudaKernel)."""

    def __init__(self, name: str, fn: Callable, out_shapes, out_dtypes,
                 grid=None, in_specs=None, out_specs=None, **kernel_kwargs):
        self.name = name
        self._fn = functools.partial(fn, **kernel_kwargs) if kernel_kwargs \
            else fn
        self._out_shapes = [tuple(s) for s in out_shapes]
        self._out_dtypes = [np.dtype(dtype_np(d)) for d in out_dtypes]
        self._grid = grid
        self._in_specs = in_specs
        self._out_specs = out_specs

    def launch(self, args: Sequence, ctx=None, grid_dims=None):
        """Run the kernel on NDArray/array inputs; returns NDArray outputs
        placed on `ctx` when given.  grid_dims overrides the bound grid
        (reference launch signature)."""
        from jax.experimental import pallas as pl
        from .ops.pallas_kernels import _interpret

        arrays = [a._handle if isinstance(a, NDArray) else a for a in args]
        out_shape = [jax.ShapeDtypeStruct(s, d) for s, d in
                     zip(self._out_shapes, self._out_dtypes)]
        if len(out_shape) == 1:
            out_shape = out_shape[0]
        kwargs = {}
        grid = grid_dims if grid_dims is not None else self._grid
        if grid is not None:
            kwargs["grid"] = grid
        if self._in_specs is not None:
            kwargs["in_specs"] = self._in_specs
        if self._out_specs is not None:
            kwargs["out_specs"] = self._out_specs
        with jax.enable_x64(False):   # grid index maps must stay i32
            outs = pl.pallas_call(
                self._fn, out_shape=out_shape,
                interpret=_interpret(*arrays), **kwargs)(*arrays)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        if ctx is not None:
            dev = ctx.jax_device if hasattr(ctx, "jax_device") else ctx
            outs = tuple(jax.device_put(o, dev) for o in outs)
        return tuple(NDArray(o) for o in outs)


class TPUModule:
    """A named collection of Pallas kernels (reference CudaModule)."""

    def __init__(self, kernels, options=(), exports=()):
        if callable(kernels):
            kernels = {kernels.__name__: kernels}
        self._kernels: Dict[str, Callable] = dict(kernels)

    def get_kernel(self, name: str, out_shapes, out_dtypes=None,
                   grid=None, in_specs=None, out_specs=None, **kernel_kwargs):
        """Bind kernel `name` to output shapes/dtypes (the role of the
        reference's C signature string)."""
        if name not in self._kernels:
            raise MXNetError("kernel %r not in module (have %s)"
                             % (name, sorted(self._kernels)))
        if out_dtypes is None:
            out_dtypes = ["float32"] * len(out_shapes)
        return TPUKernel(name, self._kernels[name], out_shapes, out_dtypes,
                         grid=grid, in_specs=in_specs, out_specs=out_specs,
                         **kernel_kwargs)


def CudaModule(*args, **kwargs):
    """Import-compat: the reference entry point.  CUDA source cannot be
    compiled for a TPU; pass Pallas kernel functions to TPUModule."""
    raise MXNetError(
        "CudaModule compiles CUDA source, which has no TPU analog; write "
        "the kernel as a Pallas function and use rtc.TPUModule instead")
