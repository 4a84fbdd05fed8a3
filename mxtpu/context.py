"""Device contexts: ``mx.tpu()``, ``mx.cpu()``, ``mx.gpu()``.

Rebuild of the reference's Context (``include/mxnet/base.h`` Context struct,
``python/mxnet/context.py`` [path cite]). A Context names a logical device;
it resolves lazily to a ``jax.Device``. ``mx.gpu()`` is kept as a
compatibility alias that resolves to the platform accelerator so reference
scripts run with ``ctx=mx.gpu()`` unchanged (the north-star swap is
``ctx=mx.tpu()``).
"""
from __future__ import annotations

import threading
from typing import List, Optional

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    """A logical device. devtype is 'cpu', 'tpu' or 'gpu'."""

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in ("cpu", "tpu", "gpu", "cpu_pinned", "cpu_shared"):
            raise ValueError(f"unknown device type {device_type!r}")
        # pinned/shared memory distinctions are meaningless under PJRT —
        # alias them to cpu (reference: src/storage/ pinned/shared managers).
        if device_type in ("cpu_pinned", "cpu_shared"):
            device_type = "cpu"
        self.device_type = device_type
        self.device_id = device_id

    # -- resolution ---------------------------------------------------------
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device."""
        devs = _devices_of_type(self.device_type)
        if not devs:
            raise RuntimeError(
                f"no {self.device_type} devices available "
                f"(jax backend: {jax.default_backend()})")
        if self.device_type == "cpu":
            # cpu ids are nominal, as in the reference: any id is
            # host memory
            return devs[self.device_id % len(devs)]
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r} is past the last of this process's "
                f"{len(devs)} accelerator device(s)")
        return devs[self.device_id]

    # -- protocol -----------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_ctx.stack.pop()

    @classmethod
    def default(cls) -> "Context":
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return _default_device()


def _devices_of_type(device_type: str) -> List[jax.Device]:
    # LOCAL devices only: under multi-process (jax.distributed) a
    # context must never resolve to another process's device — the
    # reference's ctx list was per-worker too
    all_devs = jax.local_devices()
    if device_type == "cpu":
        cpus = [d for d in all_devs if d.platform == "cpu"]
        if cpus:
            return cpus
        try:
            return jax.local_devices(backend="cpu")
        except RuntimeError:
            return []
    # 'tpu' and its compatibility alias 'gpu': the platform accelerator
    return [d for d in all_devs if d.platform != "cpu"]


def _default_device() -> Context:
    accel = [d for d in jax.devices() if d.platform != "cpu"]
    return Context("tpu", 0) if accel else Context("cpu", 0)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compatibility alias: resolves to the platform accelerator."""
    return Context("gpu", device_id)


def current_context() -> Context:
    return Context.default()


def num_tpus() -> int:
    return len([d for d in jax.devices() if d.platform != "cpu"])


def num_gpus() -> int:
    """Reference ``mx.context.num_gpus`` — counts accelerators here."""
    return num_tpus()
