"""`paddle.device` equivalent: device queries, synchronization, memory stats.

Reference: python/paddle/device/ + memory stats (paddle/fluid/memory/stats.h
surfaced as paddle.device.cuda.max_memory_allocated). On TPU, memory stats
come from jax's device memory profile.
"""

from __future__ import annotations

import jax

from ..framework.framework import (  # noqa: F401
    get_device, set_device, device_count, CPUPlace, CUDAPlace, TPUPlace,
    XPUPlace, CustomPlace, is_compiled_with_cuda, is_compiled_with_xpu,
    is_compiled_with_rocm, is_compiled_with_custom_device,
)

__all__ = ["get_device", "set_device", "device_count", "synchronize",
           "get_cudnn_version", "IPUPlace", "is_compiled_with_ipu",
           "is_compiled_with_cinn", "get_all_custom_device_type", "set_stream",
           "get_all_device_type", "get_available_device",
           "get_available_custom_device", "memory_allocated",
           "max_memory_allocated", "memory_reserved", "empty_cache", "Stream",
           "Event", "current_stream", "stream_guard", "force_cpu_backend"]


def force_cpu_backend(n_devices: int | None = None):
    """Pin jax to the host CPU backend. `n_devices` requests that many
    virtual CPU devices. Only effective before jax initializes a backend
    (JAX_PLATFORMS and XLA_FLAGS are read at init)."""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    return jax


def synchronize(device=None):
    """Block until all queued device work completes (XLA: fence via a tiny
    transfer, the analog of cudaDeviceSynchronize)."""
    (jax.device_put(0.0) + 0).block_until_ready()


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()
            if d.platform not in ("cpu", "gpu", "tpu")]


def _mem_stats(device=None):
    d = jax.devices()[0] if device is None else device
    try:
        return d.memory_stats() or {}
    except Exception:
        return {}


def memory_allocated(device=None) -> int:
    return int(_mem_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    s = _mem_stats(device)
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    s = _mem_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def empty_cache():
    pass  # XLA owns the allocator; nothing to drop (parity no-op)


class Stream:
    """Parity object: XLA schedules its own streams; recorded for API compat
    (reference: paddle.device.Stream)."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, device=None, enable_timing=False, blocking=False):
        self.device = device

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


class stream_guard:
    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        return self.stream

    def __exit__(self, *exc):
        return False


class cuda:
    """Namespace parity for paddle.device.cuda on TPU builds."""

    @staticmethod
    def max_memory_allocated(device=None):
        return max_memory_allocated(device)

    @staticmethod
    def memory_allocated(device=None):
        return memory_allocated(device)

    @staticmethod
    def max_memory_reserved(device=None):
        return memory_reserved(device)

    @staticmethod
    def memory_reserved(device=None):
        return memory_reserved(device)

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def device_count():
        return 0  # no CUDA in this build

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def get_device_name(device=None):
        """Reference device/cuda/__init__.py get_device_name; on a TPU
        build the accelerator is the TPU device."""
        import jax
        try:
            d = jax.devices()[0]
            return getattr(d, "device_kind", str(d))
        except Exception:
            return "cpu"

    @staticmethod
    def get_device_capability(device=None):
        """Reference get_device_capability returns (major, minor) compute
        capability; TPU/CPU have no CUDA CC — (0, 0) signals that like
        the reference does for unsupported devices."""
        return (0, 0)

    @staticmethod
    def get_device_properties(device=None):
        """Reference get_device_properties: a named struct with name,
        major, minor, total_memory (bytes)."""
        import collections
        import jax
        Props = collections.namedtuple(
            "_gpuDeviceProperties",
            ["name", "major", "minor", "total_memory", "multi_processor_count"])
        name = cuda.get_device_name(device)
        total = 0
        try:
            stats = jax.devices()[0].memory_stats() or {}
            total = int(stats.get("bytes_limit", 0))
        except Exception:
            pass
        return Props(name=name, major=0, minor=0, total_memory=total,
                     multi_processor_count=0)


def get_cudnn_version():
    """No cuDNN in a TPU build (reference returns None when absent)."""
    return None


class IPUPlace:
    """Name-compat placeholder (no IPU runtime in this build)."""

    def __repr__(self):
        return "Place(ipu)"


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    # XLA subsumes the CINN compiler in this build (SURVEY §7 mapping)
    return False


def get_all_custom_device_type():
    """Custom devices arrive as PJRT plugins; none registered by default."""
    return []


def set_stream(stream=None):
    """Streams are an XLA-runtime concern on TPU (no user-facing stream
    handles); accepted for script portability."""
    return stream
