"""Places and device selection.

Reference: `paddle/fluid/platform/place.h` (CPUPlace/CUDAPlace variants) and
`paddle.set_device`. TPU-native redesign: a Place names a jax device; the
default place drives `jax.default_device` so eager ops run where the user
asked without per-op copies.
"""
from __future__ import annotations

import threading

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "CUDAPinnedPlace",
    "XPUPlace", "NPUPlace", "set_device", "get_device", "parse_place",
    "default_place", "device_for", "is_compiled_with_cuda",
    "is_compiled_with_tpu", "is_compiled_with_xpu", "is_compiled_with_npu",
    "device_count", "get_cudnn_version",
]


class Place:
    """Names a device. `device()` resolves to the live jax.Device, and
    raises when this host has no such device — a place is never quietly
    resolved to some other platform."""

    kind = "cpu"
    # jax platforms this place may resolve to, in order of preference
    platforms = ("cpu",)

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def device(self) -> jax.Device:
        for plat in self.platforms:
            devs = [d for d in jax.devices() if d.platform == plat]
            if not devs:
                continue
            if self.device_id >= len(devs):
                raise RuntimeError(
                    f"{self!r}: this host has {len(devs)} {plat} "
                    f"device(s)")
            return devs[self.device_id]
        raise RuntimeError(
            f"{self!r}: no {'/'.join(self.platforms)} device on this host "
            f"(jax sees {sorted({d.platform for d in jax.devices()})})")

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    kind = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    kind = "tpu"
    platforms = ("tpu",)


class CUDAPlace(Place):
    """API-parity alias: names the accelerator (there is no CUDA in this
    framework; kept so reference code using CUDAPlace keeps working).
    Never resolves to the CPU."""
    kind = "gpu"
    platforms = ("gpu", "tpu")


class CUDAPinnedPlace(Place):
    """Pinned host memory (`platform/place.h` CUDAPinnedPlace). On TPU the
    host side is plain CPU memory — jax manages pinned staging internally —
    so this is the CPU place kept for API parity."""
    kind = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "CUDAPinnedPlace"


class XPUPlace(Place):
    """Kunlun XPU place in the reference; names the accelerator, never
    the CPU."""
    kind = "xpu"
    platforms = ("tpu", "gpu")


class NPUPlace(XPUPlace):
    kind = "npu"


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def get_cudnn_version():
    """No cuDNN on TPU; reference returns None when not compiled with CUDA
    (`python/paddle/device.py` get_cudnn_version)."""
    return None


class _State(threading.local):
    def __init__(self):
        self.place: Place | None = None


_state = _State()


def _auto_place() -> Place:
    plats = {d.platform for d in jax.devices()}
    if "tpu" in plats:
        return TPUPlace(0)
    if "gpu" in plats:
        return CUDAPlace(0)
    return CPUPlace()


def default_place() -> Place:
    if _state.place is None:
        _state.place = _auto_place()
    return _state.place


def device_for(place: Place | None = None) -> jax.Device:
    return (place or default_place()).device()


def parse_place(device: str) -> Place:
    """'cpu' | 'tpu' | 'tpu:0' | 'gpu:0' -> Place (nothing is resolved or
    selected yet)."""
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "cpu":
        return CPUPlace()
    if name in ("tpu", "xpu", "npu"):
        return TPUPlace(idx)
    if name in ("gpu", "cuda"):
        return CUDAPlace(idx)
    raise ValueError(f"Unknown device {device!r}")


def set_device(device) -> Place:
    """paddle.set_device('cpu' | 'tpu' | 'tpu:0' | 'gpu:0'). Raises when
    the host has no such device."""
    place = device if isinstance(device, Place) else parse_place(device)
    place.device()
    _state.place = place
    return place


def get_device() -> str:
    p = default_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"{p.kind}:{p.device_id}"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def device_count() -> int:
    return len(jax.devices())
