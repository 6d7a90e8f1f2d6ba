"""Device management (ref: python/paddle/device/__init__.py).

Paddle's CUDAPlace/CPUPlace become jax devices; `TPUPlace` is the
first-class accelerator. XLA owns streams/allocators, so the Paddle
stream & memory APIs map to introspection + donation hints.
"""
from __future__ import annotations

import jax


class _Place:
    def __init__(self, platform, device_id=0):
        self._platform = platform
        self._id = device_id

    def get_device_id(self):
        return self._id

    def __repr__(self):
        return f"Place({self._platform}:{self._id})"

    def __eq__(self, other):
        return (
            isinstance(other, _Place)
            and self._platform == other._platform
            and self._id == other._id
        )

    def __hash__(self):
        return hash((self._platform, self._id))

    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self._platform] or (
            jax.devices('cpu')
        )
        return devs[min(self._id, len(devs) - 1)]


class TPUPlace(_Place):
    def __init__(self, device_id=0):
        platform = jax.default_backend()
        if platform == 'cpu':
            # virtual-mesh testing: TPUPlace degrades to host devices
            super().__init__('cpu', device_id)
        else:
            super().__init__(platform, device_id)


class CPUPlace(_Place):
    def __init__(self, device_id=0):
        super().__init__('cpu', device_id)


# CUDAPlace alias: lets reference training scripts that name CUDAPlace run
# unchanged on TPU (the BASELINE north-star swap).
CUDAPlace = TPUPlace
XPUPlace = TPUPlace

_current = [None]


def set_device(device):
    """ref: paddle.device.set_device ('tpu', 'cpu', 'tpu:0', ...)."""
    if isinstance(device, _Place):
        _current[0] = device
        return device
    name, _, idx = str(device).partition(':')
    idx = int(idx) if idx else 0
    if name in ('tpu', 'gpu', 'cuda', 'xpu'):
        _current[0] = TPUPlace(idx)
    else:
        _current[0] = CPUPlace(idx)
    return _current[0]


def get_device():
    if _current[0] is None:
        _current[0] = TPUPlace(0)
    p = _current[0]
    return f"{p._platform}:{p._id}"


def get_default_place():
    if _current[0] is None:
        _current[0] = TPUPlace(0)
    return _current[0]


def device_count(platform=None):
    return jax.device_count()


def local_device_count():
    return jax.local_device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return jax.default_backend() not in ('cpu',)


class cuda:
    """Namespace parity for paddle.device.cuda memory stats."""

    @staticmethod
    def memory_allocated(device=None):
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get('bytes_in_use', 0)

    @staticmethod
    def max_memory_allocated(device=None):
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get('peak_bytes_in_use', 0)

    @staticmethod
    def empty_cache():
        return None

    @staticmethod
    def synchronize(device=None):
        for d in jax.live_arrays():
            d.block_until_ready()


def synchronize():
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()


# ---- feature probes & stream compat (ref: python/paddle/device) -----------
# CUDA/ROCm/IPU/CINN probes answer honestly for a TPU/XLA build; the
# stream API maps onto XLA's implicit async dispatch (one compute stream
# per device, synchronization via block_until_ready).


def get_cudnn_version():
    """ref: paddle.device.get_cudnn_version — None: no cuDNN here."""
    return None


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    """CINN's role (graph compilation) is played by XLA, but the CINN
    binary itself is not present."""
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_distribute():
    """Distributed is always available (XLA collectives are built in)."""
    return True


def is_compiled_with_custom_device(device_type=None):
    return False


IPUPlace = CPUPlace  # accepted for script compat; degrades to host


def get_all_device_type():
    import jax

    return sorted({d.platform for d in jax.devices()} | {'cpu'})


def get_all_custom_device_type():
    return []


def get_available_device():
    import jax

    return [f'{d.platform}:{d.id}' for d in jax.devices()]


def get_available_custom_device():
    return []


class Stream:
    """ref: paddle.device.Stream. XLA runs one ordered async compute
    stream per device; this object names it for API compatibility and
    `synchronize` drains it."""

    def __init__(self, device=None, priority=2):
        self.device = device
        self.priority = priority

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        event.synchronize()

    def wait_stream(self, stream):
        stream.synchronize()

    def record_event(self, event=None):
        event = event or Event()
        event.record(self)
        return event

    def query(self):
        return True


class Event:
    """ref: paddle.device.Event — completion marker on the XLA stream."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self._recorded = False

    def record(self, stream=None):
        self._recorded = True

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_current_stream = [None]


def current_stream(device=None):
    if _current_stream[0] is None:
        _current_stream[0] = Stream(device)
    return _current_stream[0]


def set_stream(stream):
    prev = current_stream()
    _current_stream[0] = stream
    return prev


class stream_guard:
    """ref: paddle.device.stream_guard — context switching the current
    stream (a no-op ordering-wise: XLA keeps program order)."""

    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        self._prev = set_stream(self.stream)
        return self.stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False
