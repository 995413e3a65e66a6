"""Device / place management.

The reference dispatches kernels on ``phi::Place`` (CPU/GPU/XPU/Custom —
/root/reference/paddle/phi/common/place.h:28) with a DeviceContext pool and
per-place allocators. On TPU the XLA runtime owns devices, streams and memory,
so a Place reduces to a handle onto a ``jax.Device``; ``set_device`` installs a
default placement used by creation ops.
"""
from __future__ import annotations

import threading

import jax

_state = threading.local()


class Place:
    """Device identity: ``Place("tpu", 0)``, ``Place("cpu")``.

    TPU-native analogue of ``phi::Place``: no allocation-type axis (XLA owns
    memory), just a backend name + device index.
    """

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str = "tpu", device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    @property
    def jax_device(self) -> jax.Device:
        # jax.devices(name) raises RuntimeError when this process has no such
        # backend: a TPU place never resolves to a CPU device
        devs = jax.devices(self.device_type)
        if self.device_id >= len(devs):
            raise RuntimeError(
                f"{self!r}: only {len(devs)} {self.device_type!r} device(s)")
        return devs[self.device_id]

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

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


CPUPlace = lambda: Place("cpu", 0)  # noqa: E731 - paddle-API-shaped constructors
TPUPlace = lambda idx=0: Place("tpu", idx)  # noqa: E731


def set_device(device: str) -> Place:
    """``paddle.device.set_device("tpu:0")`` equivalent."""
    if ":" in device:
        dev_type, _, idx = device.partition(":")
        place = Place(dev_type, int(idx))
    else:
        place = Place(device, 0)
    _state.place = place
    return place


def get_device() -> str:
    place = get_place()
    return f"{place.device_type}:{place.device_id}"


def get_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        place = Place(jax.default_backend(), 0)
        _state.place = place
    return place


def device_count(device_type: str | None = None) -> int:
    if device_type is None:
        return jax.device_count()
    return len([d for d in jax.devices() if d.platform == device_type])


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())
