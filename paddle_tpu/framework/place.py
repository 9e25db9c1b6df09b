"""Device placement.

TPU-native equivalent of the reference Place taxonomy
(`/root/reference/paddle/fluid/platform/place.h`) and
`paddle.set_device` (`python/paddle/device/__init__.py`). Places map onto
`jax.Device` objects; the default device is process-global, mirroring the
reference's `DeviceContextPool` current-device semantics.
"""
from __future__ import annotations

import jax


class Place:
    """Base place. Wraps a jax.Device."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    @property
    def jax_device(self) -> jax.Device:
        # jax.devices(<platform>) raises when this process has no such
        # backend: a TPUPlace on a host without the chip is an error, not
        # whatever device happens to exist
        devs = jax.devices(self.device_type)
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: this process has {len(devs)} "
                f"{self.device_type!r} device(s)")
        return devs[self.device_id]

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    device_type = "tpu"


# Aliases kept for API familiarity with the reference's device taxonomy
# (`platform/place.h`): on this framework the accelerator is a TPU, and
# "pinned" host memory is ordinary host memory (XLA stages its own
# transfers).
CUDAPlace = TPUPlace
NPUPlace = TPUPlace


class CUDAPinnedPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


_expected_place: Place | None = None


def set_device(device) -> Place:
    """paddle.set_device('tpu:0' | 'cpu' | 'tpu')."""
    global _expected_place
    if isinstance(device, Place):
        _expected_place = device
        return device
    if not isinstance(device, str):
        raise TypeError(f"device must be str or Place, got {type(device)}")
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "cpu":
        _expected_place = CPUPlace()
    elif name in ("tpu", "gpu", "cuda", "xpu", "npu"):
        # accelerator names all route to the TPU on this framework
        _expected_place = TPUPlace(idx)
    else:
        _expected_place = CustomPlace(name, idx)
    return _expected_place


def get_device() -> str:
    p = get_expected_place()
    return f"{p.device_type}:{p.device_id}" if p.device_type != "cpu" else "cpu"


def get_expected_place() -> Place:
    global _expected_place
    if _expected_place is None:
        _expected_place = (CPUPlace() if jax.default_backend() == "cpu"
                           else TPUPlace(0))
    return _expected_place


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() == "tpu"


def device_count() -> int:
    return len(jax.devices())
