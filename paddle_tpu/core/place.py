"""Device places.

Analog of `phi::Place` (`paddle/phi/common/place.h`) and
`paddle.set_device`. On TPU there is no per-op stream management — XLA owns
scheduling — so a Place is just a binding to a jax.Device used as the default
placement for newly created tensors.
"""
from __future__ import annotations

import jax


class Place:
    def __init__(self, device: "jax.Device"):
        self.device = device

    @property
    def platform(self) -> str:
        return self.device.platform

    def is_cpu_place(self) -> bool:
        return self.device.platform == "cpu"

    def is_tpu_place(self) -> bool:
        return self.device.platform == "tpu"

    def __repr__(self):
        return f"Place({self.device.platform}:{self.device.id})"

    def __eq__(self, other):
        return isinstance(other, Place) and self.device == other.device

    def __hash__(self):
        return hash(self.device)


def CPUPlace() -> Place:
    return Place(jax.devices("cpu")[0])


def TPUPlace(idx: int = 0) -> Place:
    devs = _platform_devices("tpu")
    return Place(devs[idx])


_current_place: Place | None = None


def _platform_devices(platform: str):
    """Devices for a user-facing platform name ('tpu', 'cpu')."""
    return jax.devices(platform)


def set_device(device: str) -> Place:
    """paddle.set_device analog: 'tpu', 'tpu:1', 'cpu'."""
    global _current_place
    if ":" in device:
        platform, idx = device.split(":")
        idx = int(idx)
    else:
        platform, idx = device, 0
    dev = _platform_devices(platform)[idx]
    jax.config.update("jax_default_device", dev)
    _current_place = Place(dev)
    return _current_place


def get_device() -> str:
    p = current_place()
    plat = "tpu" if p.is_tpu_place() else p.platform
    return f"{plat}:{p.device.id}" if plat != "cpu" else "cpu"


def current_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = Place(jax.devices()[0])
    return _current_place


def is_compiled_with_tpu() -> bool:
    try:
        return len(_platform_devices("tpu")) > 0
    except RuntimeError:
        return False
