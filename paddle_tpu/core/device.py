"""Device / place management.

Analog of the reference Place + DeviceContext pool
(paddle/phi/core/device_context.h, paddle/phi/backends/context_pool.cc).
On TPU the runtime (PJRT) owns streams and contexts; what remains is
device selection and placement queries.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax


class Place:
    """A device place, e.g. TPUPlace(0) / CPUPlace()."""

    def __init__(self, device: jax.Device):
        self._device = device

    @property
    def device(self) -> jax.Device:
        return self._device

    def is_cpu_place(self) -> bool:
        return self._device.platform == "cpu"

    def is_tpu_place(self) -> bool:
        return self._device.platform == "tpu"

    def __repr__(self):
        return f"Place({self._device.platform}:{self._device.id})"

    def __eq__(self, other):
        return isinstance(other, Place) and self._device == other._device

    def __hash__(self):
        return hash(self._device)


class CPUPlace(Place):
    def __init__(self, idx: int = 0):
        super().__init__(_cpu_devices()[idx])


class TPUPlace(Place):
    """Device ``idx`` of the default backend, which must be a TPU: asking
    for a TPU where JAX found none is an error, never another device."""

    def __init__(self, idx: int = 0):
        dev = jax.devices()[idx]
        if dev.platform != "tpu":
            raise RuntimeError(
                f"no TPU: JAX's default backend is {dev.platform!r} "
                f"({dev.device_kind})")
        super().__init__(dev)


@functools.lru_cache(None)
def _cpu_devices():
    return jax.devices("cpu")


_current_device: Optional[Place] = None


def _parse_place(name: str) -> Place:
    """Parse "cpu", "tpu", "tpu:1" (gpu/xpu accepted for API compat)."""
    if ":" in name:
        kind, idx = name.split(":")
        idx = int(idx)
    else:
        kind, idx = name, 0
    if kind == "cpu":
        return CPUPlace(idx)
    if kind == "tpu":
        return TPUPlace(idx)
    if kind in ("gpu", "xpu"):
        return Place(jax.devices()[idx])
    raise ValueError(f"unknown device {name!r}")


def set_device(device) -> Place:
    """paddle.set_device("tpu" | "tpu:0" | "cpu")."""
    global _current_device
    _current_device = device if isinstance(device, Place) else _parse_place(str(device))
    return _current_device


def get_device() -> Place:
    global _current_device
    if _current_device is None:
        _current_device = Place(jax.devices()[0])
    return _current_device


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


# -- memory stats & synchronization (reference paddle.device.cuda.* —
# memory_allocated/max_memory_allocated, synchronize; stats from the PJRT
# device where available, else the native stat registry csrc/stats.cc) ------

def synchronize(device=None) -> None:
    """Block until all queued device work finishes (XLA orders execution, so
    this is a fence: round-trip a tiny computation)."""
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


def _device_memory_stats(device=None) -> dict:
    dev = (device.device if isinstance(device, Place) else
           get_device().device)
    stats = getattr(dev, "memory_stats", lambda: None)()
    return stats or {}


def _live_bytes() -> int:
    """Fallback when PJRT exposes no memory_stats: sum live jax buffers and
    record into the native stat registry (keeps a running peak)."""
    import jax as _jax
    from ..native import stats as nstats
    cur = sum(int(getattr(a, "nbytes", 0)) for a in _jax.live_arrays())
    nstats.update("Allocated:device", cur - nstats.current("Allocated:device"))
    return cur


def memory_allocated(device=None) -> int:
    """Bytes currently held by live buffers on the device."""
    stats = _device_memory_stats(device)
    if "bytes_in_use" in stats:
        return int(stats["bytes_in_use"])
    return _live_bytes()


def max_memory_allocated(device=None) -> int:
    stats = _device_memory_stats(device)
    if "peak_bytes_in_use" in stats:
        return int(stats["peak_bytes_in_use"])
    _live_bytes()  # refresh the running peak
    from ..native import stats as nstats
    return nstats.peak("Allocated:device")


def memory_reserved(device=None) -> int:
    # PJRT exposes bytes_reserved on some platforms; bytes_limit is CAPACITY,
    # not reservation — falling back to allocated is the honest number
    stats = _device_memory_stats(device)
    if "bytes_reserved" in stats:
        return int(stats["bytes_reserved"])
    return memory_allocated(device)


def max_memory_reserved(device=None) -> int:
    return max(memory_reserved(device), max_memory_allocated(device))


def empty_cache() -> None:
    """Reference paddle.device.cuda.empty_cache; XLA owns the buffer pool —
    no-op kept for API parity."""


class Stream:
    """No-op stream (reference paddle.device.Stream): XLA schedules; kept so
    stream-annotated code ports cleanly."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize(self.device)


class Event:
    """No-op event (reference paddle.device.Event)."""

    def __init__(self, enable_timing=False):
        self._t = None

    def record(self, stream=None):
        import time
        synchronize()
        self._t = time.perf_counter()

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end: "Event") -> float:
        return (end._t - self._t) * 1e3 if self._t and end._t else 0.0
