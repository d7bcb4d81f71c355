"""Device/place abstraction.

TPU-native analogue of the reference's ``Place`` hierarchy
(``paddle/phi/common/place.h``) and ``paddle.device.set_device``
(``python/paddle/device/__init__.py``). A Place wraps a PJRT device handle
(`jax.Device`); there is no per-device context pool — XLA owns streams.
"""
from __future__ import annotations

import threading

import jax


class Place:
    """A logical device. ``device_type`` is 'cpu' | 'tpu' | 'gpu'."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    # -- PJRT handle ------------------------------------------------------
    def jax_device(self):
        """The ``jax.Device`` this place names. A place that is not there is
        an error: running somewhere else would be a silent change of device."""
        if self.device_type == "cpu":
            # the host backend exists beside an accelerator default too
            devs = jax.devices("cpu")
        else:
            devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not 0 <= self.device_id < len(devs):
            have = sorted({d.platform for d in jax.devices()})
            raise RuntimeError(
                f"{self!r} is not available: this process has {len(devs)} "
                f"{self.device_type} device(s) (default backend "
                f"{jax.default_backend()!r}, platforms {have})")
        return devs[self.device_id]

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPlace(Place):  # accepted for API parity; maps to gpu backend
    def __init__(self, device_id: int = 0):
        super().__init__("gpu", device_id)


_state = threading.local()


def _default_place() -> Place:
    plat = jax.default_backend()
    if plat == "tpu":
        return TPUPlace(0)
    if plat == "gpu":
        return CUDAPlace(0)
    return CPUPlace()


def set_device(device: str) -> Place:
    """paddle.device.set_device('tpu:0' | 'cpu' | 'gpu:1')."""
    if isinstance(device, Place):
        _state.place = device
        return device
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = {"xla": "tpu"}.get(name, name)
    if name == "cpu":
        place = CPUPlace()
    elif name == "tpu":
        place = TPUPlace(idx)
    elif name in ("gpu", "cuda"):
        place = CUDAPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}")
    _state.place = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    if not hasattr(_state, "place"):
        _state.place = _default_place()
    return _state.place


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


class CUDAPinnedPlace(CPUPlace):
    """Pinned host memory place (reference platform/place.h). On this
    runtime host staging is the arena allocator's job; the class exists for
    API parity and behaves as host memory."""


class _UnavailablePlace:
    """Reference device places with no backing hardware here (IPU/MLU/NPU/
    XPU/custom). Constructing one fails loudly instead of silently running
    on the wrong device."""

    def __init__(self, *a, **k):
        raise RuntimeError(
            f"{type(self).__name__} hardware is not available in this "
            "TPU-native build; use CPUPlace() or TPUPlace()")


class IPUPlace(_UnavailablePlace):
    pass


class MLUPlace(_UnavailablePlace):
    pass


class NPUPlace(_UnavailablePlace):
    pass


class XPUPlace(_UnavailablePlace):
    pass


class CustomPlace(_UnavailablePlace):
    pass
