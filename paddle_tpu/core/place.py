"""Device places — the TPU-native successor of ``paddle/platform/place.h``.

The reference models devices as a ``boost::variant<CPUPlace, GPUPlace>``
(``paddle/platform/place.h:24-55``) with a per-place ``DeviceContext`` carrying
streams and cuBLAS/cuDNN handles (``device_context.h:38-94``).  On TPU the
equivalents are ``jax.Device`` objects from the PJRT client; there are no
streams or library handles to manage (XLA owns scheduling), so a Place here is
a thin, hashable selector that resolves to a concrete ``jax.Device`` and acts
as the target for ``jax.device_put`` / jit placement.
"""

from __future__ import annotations

import dataclasses
import functools

import jax


@dataclasses.dataclass(frozen=True)
class Place:
    """Base device selector. ``device_id`` indexes into the platform's devices."""

    device_id: int = 0

    platform: str = ""  # overridden by subclasses

    def device(self) -> jax.Device:
        """The ``device_id``-th device of this place's platform.  Raises
        when jax lists none: a place never resolves to another
        platform's device, so a run that asked for a TPU cannot end up
        on the CPU unnoticed."""
        devs = [d for d in jax.devices() if d.platform == self.platform]
        if not devs:
            raise RuntimeError(
                f"{self!r}: no {self.platform!r} device among "
                f"{[d.platform for d in jax.devices()]} (jax.devices())")
        return devs[self.device_id % len(devs)]

    def __repr__(self) -> str:  # e.g. TPUPlace(0)
        return f"{type(self).__name__}({self.device_id})"


@dataclasses.dataclass(frozen=True, repr=False)
class CPUPlace(Place):
    platform: str = "cpu"


@dataclasses.dataclass(frozen=True, repr=False)
class TPUPlace(Place):
    """TPU device selector (the reference's GPUPlace analog, CUDA-free)."""

    platform: str = "tpu"


@functools.cache
def is_compiled_with_tpu() -> bool:
    """True when jax lists a TPU device, analogous to the reference's
    ``WITH_GPU`` build flag + ``hl_get_device_count`` probe.  Lazy and
    cached: importing this module never initialises a backend (a launcher
    parent must leave the chip to its children)."""
    return any(d.platform == "tpu" for d in jax.devices())


_default_place: Place | None = None


def set_default_place(place: Place) -> None:
    global _default_place
    _default_place = place


def default_place() -> Place:
    """The place used when none is given — TPU if jax lists one, else CPU
    (reference: gflag ``use_gpu`` in ``paddle/utils/Flags.h:19``).  This
    is a choice of default, stated by the returned type; code that must
    run on the chip asks for ``TPUPlace()`` and lets it raise."""
    if _default_place is not None:
        return _default_place
    return TPUPlace() if is_compiled_with_tpu() else CPUPlace()
