"""Device context — TPU-native analog of the reference's Context
(include/mxnet/base.h:142-247).

On the reference, Context selects a CUDA device and every NDArray op ships a
kernel to that device's stream.  Here a Context names a JAX device; arrays are
committed to it with jax.device_put and XLA owns streams/async.  ``tpu`` is
the first-class device type; ``gpu(i)`` is accepted and mapped onto the i-th
accelerator so reference scripts run unmodified; ``cpu()`` is the host.
A context that names an accelerator this process does not have raises when
it is resolved: ``tpu(0)`` is never quietly the CPU, ``tpu(3)`` on one chip
is never chip 0.
"""
from __future__ import annotations

import threading
from typing import List, Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "device_of", "device_summary"]


def _accelerators():
    # local_devices: in a multi-process run only this rank's devices are
    # addressable (jax.devices() lists the whole job's)
    return [d for d in jax.local_devices() if d.platform != "cpu"]


class Context:
    """Named device. devtype 'cpu'|'tpu'|'gpu'|'cpu_pinned'|'cpu_shared'."""

    # reference keeps int enum (base.h:147-153); keep names + ids for parity
    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devid2type = {v: k for k, v in devtype2id.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if isinstance(device_type, int):
            device_type = Context.devid2type[device_type]
        self.device_type = device_type
        self.device_id = int(device_id)
        self._old_ctx: Optional[Context] = None

    @property
    def device_typeid(self) -> int:
        return Context.devtype2id[self.device_type]

    @property
    def jax_device(self):
        """Resolve to a concrete jax device."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                cpus = [d for d in jax.local_devices()
                        if d.platform == "cpu"] or jax.devices("cpu")
                return cpus[self.device_id % len(cpus)]
            except RuntimeError:
                # cpu platform absent under some runtimes: fall back to default
                return jax.local_devices()[0]
        acc = _accelerators()
        if not 0 <= self.device_id < len(acc):
            raise MXNetError(
                "context %s names accelerator %d but this process has %d "
                "(jax.local_devices(): %s)"
                % (self, self.device_id, len(acc),
                   [str(d) for d in jax.local_devices()]))
        return acc[self.device_id]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        Context._default_ctx.value = self._old_ctx

    def empty_cache(self):
        """Parity with reference Context.empty_cache; XLA owns the allocator."""
        try:
            for buf in jax.live_arrays():
                pass  # XLA's BFC allocator frees on GC; nothing to do eagerly
        except Exception:
            pass

    @classmethod
    def default_ctx(cls) -> "Context":
        if not hasattr(cls._default_ctx, "value"):
            cls._default_ctx.value = Context("cpu", 0)
        return cls._default_ctx.value


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accepted for reference-script compatibility; maps to the i-th
    accelerator (on a TPU host that is a TPU chip)."""
    return Context("gpu", device_id)


def num_gpus() -> int:
    # local count: in a multi-process job only this rank's chips are
    # addressable, and contexts enumerate local devices (_accelerators)
    return len([d for d in jax.local_devices() if d.platform != "cpu"])


def num_tpus() -> int:
    return num_gpus()


def device_summary(devices) -> dict:
    """``{"platform", "kind", "count"}`` of ``devices`` as jax reports them:
    the block every benchmark result and the chip smoke name their device
    with."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def current_context() -> Context:
    return Context.default_ctx()


def device_of(array) -> Context:
    """Context of a jax array."""
    try:
        dev = list(array.devices())[0]
    except Exception:
        return cpu()
    if dev.platform == "cpu":
        return cpu()
    return tpu(_accelerators().index(dev))
