"""Device contexts.

Reference surface: ``python/mxnet/context.py`` — ``Context``, ``cpu()``,
``gpu(i)``, ``cpu_pinned()``, ``num_gpus``, default-context stack (SURVEY.md
§3.2 "context").  TPU-native mapping: a ``Context`` names a ``jax.Device``;
``mx.tpu(i)`` is first-class and ``mx.gpu(i)`` aliases the i-th accelerator so
reference scripts run unchanged.  Pinned/shared CPU variants map to plain host
memory (XLA manages transfers; there is no user-visible pinned pool on TPU).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = [
    "Context", "cpu", "gpu", "tpu", "cpu_pinned", "cpu_shared",
    "num_gpus", "num_tpus", "current_context", "gpu_memory_info",
]


class Context:
    """A device context. ``devtype`` in {'cpu','tpu','gpu','cpu_pinned',
    'cpu_shared'}; 'gpu' is an alias for the local accelerator (TPU here)."""

    _default_ctx = threading.local()

    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devid2type = {v: k for k, v in devtype2id.items()}

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in self.devtype2id:
            raise MXNetError(f"unknown device type {device_type}")
        self.device_type = device_type
        self.device_id = device_id

    # -- identity ----------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devtype2id[self.device_type]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- jax mapping -------------------------------------------------------
    def jax_device(self) -> jax.Device:
        """Resolve to the concrete jax.Device backing this context.

        Always resolves within THIS process's addressable devices
        (``jax.local_devices``) — under multi-process SPMD the global
        device list leads with other hosts' devices, which cannot be
        device_put targets (SURVEY.md §4.4 process boundaries)."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _local_devices("cpu") if _has_platform("cpu") \
                else _local_devices()
            return devs[min(self.device_id, len(devs) - 1)]
        accel = _accel_devices()
        if not accel:
            raise MXNetError(
                f"context {self}: no accelerator visible to jax (platform "
                f"found: {jax.default_backend()}); use mx.cpu() to run on "
                "the host")
        if self.device_id >= len(accel):
            raise MXNetError(
                f"context {self} out of range: {len(accel)} device(s) visible")
        return accel[self.device_id]

    # -- default-context stack --------------------------------------------
    @classmethod
    def default_ctx(cls) -> "Context":
        return getattr(cls._default_ctx, "value", None) or _default_context()

    def __enter__(self):
        self._old = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, *args):
        Context._default_ctx.value = self._old

    def empty_cache(self):
        """Reference: ``Context.empty_cache`` frees the GPU pool; XLA owns
        HBM on TPU so this is a no-op."""


def _has_platform(name: str) -> bool:
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


_ACCEL_CACHE = None


def _local_devices(platform: str = None):
    """This process's addressable devices, optionally of one backend."""
    return jax.local_devices(backend=platform)


def _accel_devices():
    """Non-CPU jax devices addressable by this process, else empty."""
    global _ACCEL_CACHE
    if _ACCEL_CACHE is None:
        devs = [d for d in _local_devices() if d.platform != "cpu"]
        _ACCEL_CACHE = devs
    return _ACCEL_CACHE


def _default_context() -> Context:
    return Context("tpu", 0) if _accel_devices() else Context("cpu", 0)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context("cpu_shared", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for the local accelerator so reference scripts using
    ``mx.gpu(i)`` target TPU chip *i* here."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    return len(_accel_devices())


def num_tpus() -> int:
    return len(_accel_devices())


def current_context() -> Context:
    return Context.default_ctx()


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes of device HBM from the backend allocator
    (``device.memory_stats``; reference: ``mx.context.gpu_memory_info``).
    Raises ``MXNetError`` without an accelerator, like any accelerator
    context."""
    dev = Context("tpu", device_id).jax_device()
    stats = dev.memory_stats()
    if not stats or not stats.get("bytes_limit"):
        raise MXNetError(f"{dev} reports no allocator stats")
    total = stats["bytes_limit"]
    return (max(total - stats.get("bytes_in_use", 0), 0), total)
