"""Device/place model.

TPU-native analogue of the reference's Place variant
(/root/reference/paddle/fluid/platform/place.h:26-130: CPUPlace, CUDAPlace,
XPUPlace, boost::variant Place) and DeviceContextPool
(platform/device_context.h:623). On TPU the whole L0 platform layer collapses
onto jax.Device / the PJRT client: a Place is a thin named handle resolving to
a jax.Device; streams/handles/contexts are owned by XLA.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base place: identifies a device a Tensor lives on."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    # -- jax bridge ---------------------------------------------------------
    def get_device(self):
        """Resolve to a jax.Device (falls back to default backend)."""
        devs = _devices_of(self.device_type)
        if not devs:
            devs = jax.devices()
        return devs[min(self.device_id, len(devs) - 1)]

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    """A TPU chip (reference analogue: the XPUPlace+BKCL pairing,
    platform/place.h:62 — the in-repo model for a non-CUDA accelerator)."""
    device_type = "tpu"


# The reference exposes CUDAPlace ubiquitously; map it onto the accelerator
# backend so reference-style code (`paddle.CUDAPlace(0)`) runs unchanged.
class XLAPlace(TPUPlace):
    device_type = "tpu"


CUDAPlace = XLAPlace

# reference platform/place.h:62 XPUPlace (Kunlun accelerator): map onto
# THE accelerator backend here too — on this stack that is the TPU chip
XPUPlace = XLAPlace


class CUDAPinnedPlace(CPUPlace):
    """Pinned host memory is a PJRT implementation detail; alias of CPU."""


@functools.lru_cache(maxsize=None)
def _accelerator_platform():
    """Platform name of this process's default backend. A backend that
    fails to initialise raises here: answering "cpu" would hide it."""
    return jax.default_backend()


@functools.lru_cache(maxsize=None)
def _devices_of(device_type: str):
    if device_type == "cpu":
        try:
            return tuple(jax.devices("cpu"))
        except RuntimeError:
            return tuple(jax.devices())
    # 'tpu' (or any accelerator request) → default backend devices
    return tuple(jax.devices())


_current_place = None


def set_device(device: str):
    """paddle.set_device — 'cpu', 'tpu', 'tpu:0', 'gpu:0' (gpu→accelerator)."""
    global _current_place
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if name == "cpu":
        _current_place = CPUPlace()
    elif name in ("tpu", "xla", "gpu", "cuda", "npu", "xpu"):
        _current_place = TPUPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}")
    return _current_place


def get_device() -> str:
    p = _default_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"{p.device_type}:{p.device_id}"


def _default_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = (
            TPUPlace(0) if _accelerator_platform() != "cpu" else CPUPlace())
    return _current_place


def is_compiled_with_cuda() -> bool:
    # For API parity; reports whether an accelerator backend is present.
    return False


def is_compiled_with_tpu() -> bool:
    return _accelerator_platform() not in ("cpu",)


def device_count() -> int:
    return len(jax.devices())
