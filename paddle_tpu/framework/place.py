"""Device/Place abstraction.

TPU-native equivalent of the reference Place variants
(/root/reference/paddle/fluid/platform/place.h CPUPlace/CUDAPlace/...)
and DeviceContextPool (platform/device_context.h:550): a Place names a jax
device; the "device context" (streams, handles) is owned by XLA, so the
pool degenerates to a device lookup.
"""
from __future__ import annotations

import jax


class Place:
    """Names a physical device. Equality is structural."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        devs = _devices(self.device_type)
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: no such device; jax reports {len(devs)} "
                f"{self.device_type} device(s) (default backend "
                f"{jax.default_backend()!r})")
        return devs[self.device_id]


def _devices(device_type):
    """The live ``device_type`` devices. The host cpu backend exists beside
    an accelerator one, so a CPUPlace resolves there too; no other platform
    stands in for the one asked for."""
    if device_type == "cpu" and jax.default_backend() != "cpu":
        return jax.devices("cpu")
    return [d for d in jax.devices() if d.platform == device_type]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


# API-parity aliases: CUDA code written against the reference maps onto TPU.
class CUDAPlace(TPUPlace):
    pass


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


def is_compiled_with_tpu() -> bool:
    return bool(_devices("tpu"))


def is_compiled_with_cuda() -> bool:
    return False


def get_device() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def set_device(device: str) -> Place:
    """Accepts 'tpu', 'tpu:0', 'cpu', 'cpu:1'."""
    if ":" in device:
        kind, _, idx = device.partition(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    kind = kind.lower()
    if kind in ("tpu", "gpu", "cuda", "xpu"):
        place = TPUPlace(idx)
    elif kind == "cpu":
        place = CPUPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}")
    _default_place[0] = place
    return place


def device_count(device_type: str = "tpu") -> int:
    return len(_devices(device_type)) or 1


_default_place = [None]


def get_default_place() -> Place:
    if _default_place[0] is None:
        _default_place[0] = TPUPlace(0) if is_compiled_with_tpu() else CPUPlace(0)
    return _default_place[0]
