"""Device / Place abstraction.

Reference: paddle/fluid/platform/place.h defines CPUPlace/CUDAPlace/... variants with
visitor dispatch, and DeviceContextPool owns per-place streams/handles
(platform/device_context.h). On TPU, XLA/PJRT owns streams and contexts, so a Place
here is just a named handle onto a `jax.Device`; there is no user-visible stream.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """A named device handle; resolves lazily to a jax.Device."""

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind  # "cpu" | "tpu" | "gpu"
        self.index = index

    def jax_device(self) -> jax.Device:
        devs = _devices_of_kind(self.kind)
        if not devs:
            # Fall back to default backend (e.g. asking for tpu on a CPU-only host).
            devs = jax.devices()
        return devs[self.index % len(devs)]

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.index == other.index
        )

    def __hash__(self):
        return hash((self.kind, self.index))


class CPUPlace(Place):
    def __init__(self, index: int = 0):
        super().__init__("cpu", index)


class TPUPlace(Place):
    def __init__(self, index: int = 0):
        super().__init__("tpu", index)


# CUDA alias kept for API familiarity; resolves to the accelerator backend.
class CUDAPlace(Place):
    def __init__(self, index: int = 0):
        super().__init__("gpu", index)


class CUDAPinnedPlace(Place):
    """Compat alias (place.h CUDAPinnedPlace): pinned host staging is a
    CUDA-era concept; on TPU the host side is just CPU memory — so this
    place IS the cpu kind (a batch staged here must not land on the
    accelerator)."""

    def __init__(self):
        super().__init__("cpu", 0)


class NPUPlace(Place):
    """Compat alias (place.h NPUPlace): accepted for API parity; Ascend is
    a non-goal backend (SURVEY), so it resolves to host CPU rather than
    silently claiming the TPU."""

    def __init__(self, index: int = 0):
        super().__init__("cpu", index)


@functools.lru_cache(maxsize=None)
def _devices_of_kind(kind: str):
    all_devices = jax.devices()
    if kind == "cpu":
        return tuple(d for d in all_devices if d.platform == "cpu") or tuple(
            jax.devices("cpu")
        )
    # Any non-cpu platform (tpu, gpu) counts as the accelerator.
    accel = tuple(d for d in all_devices if d.platform != "cpu")
    return accel


_CURRENT_DEVICE = [None]


def set_device(device):
    """paddle.set_device('cpu'|'tpu'|'tpu:0') analog."""
    if isinstance(device, Place):
        _CURRENT_DEVICE[0] = device
        return device
    kind, _, idx = str(device).partition(":")
    if kind in ("gpu", "cuda", "tpu", "xla"):
        kind = "tpu"
    place = Place(kind, int(idx) if idx else 0)
    _CURRENT_DEVICE[0] = place
    return place


def get_device() -> Place:
    if _CURRENT_DEVICE[0] is None:
        default = jax.devices()[0]
        _CURRENT_DEVICE[0] = Place(
            "cpu" if default.platform == "cpu" else "tpu", 0
        )
    return _CURRENT_DEVICE[0]


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform != "cpu" for d in jax.devices())


def device_count() -> int:
    return jax.device_count()
