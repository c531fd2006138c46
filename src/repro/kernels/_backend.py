"""Backend resolution shared by the Pallas kernel frontends.

The kernels in this package are written against ``pallas.tpu``: they
compile through Mosaic on a TPU backend and run under the Pallas
interpreter on the CPU, which is the test path.  ``resolve_interpret``
makes that decision in one place.  Any other backend (gpu, rocm, plugin
devices) is refused with an error: interpreting there would run the chip's
kernels at host speed while the caller believes it is on an accelerator.

A kernel that still runs interpreted on a non-CPU backend (an explicit
``interpret=True`` on a TPU) records the ``interpret_fallback`` obs
counter and event, so a measurement can assert that none of its launches
left the chip's compiler.

``checked_vmem_limit`` checks a compiled kernel's ``vmem_limit_bytes``
against the attached device's VMEM from the capacity table in
:mod:`repro.core.tiling`; an unknown device kind is an error there,
never a default.
"""

from __future__ import annotations

import jax

from .. import obs
from ..core.tiling import vmem_capacity_bytes

__all__ = ["checked_vmem_limit", "device_kind", "resolve_interpret"]


class UnsupportedBackendError(RuntimeError):
    """The active JAX backend can neither compile nor test the kernels."""


def resolve_interpret(
    interpret: bool | None, kernel: str | None = None
) -> bool:
    """Resolve the ``interpret=None`` default against the active backend.

    * TPU -> compiled kernels (``False``);
    * CPU -> interpreter (``True``), the test path;
    * anything else -> :class:`UnsupportedBackendError`.

    An explicit True/False is honored on the TPU and the CPU.  ``kernel``
    names the calling frontend (``"stencil"``, ``"conv1d"``) for the
    error and the obs event.
    """
    backend = jax.default_backend()
    name = kernel or "<unnamed>"
    if backend not in ("tpu", "cpu"):
        raise UnsupportedBackendError(
            f"backend {backend!r} cannot run the Pallas TPU kernel {name}: "
            "it compiles only for a TPU, and interprets only on the CPU "
            "(set JAX_PLATFORMS=cpu to test there)"
        )
    resolved = (backend == "cpu") if interpret is None else bool(interpret)
    if resolved and backend != "cpu":
        obs.add("interpret_fallback")
        if obs.enabled():
            obs.event("interpret_fallback", backend=backend, kernel=name)
    return resolved


def device_kind() -> str:
    """``device_kind`` of the first attached device."""
    return jax.devices()[0].device_kind


def checked_vmem_limit(need: int) -> int:
    """``vmem_limit_bytes`` for a compiled kernel that needs ``need``
    bytes (``core.tiling.kernel_vmem_bytes``): refused here, with its
    size, when the attached core has less VMEM, rather than by the
    compiler."""
    cap = vmem_capacity_bytes(device_kind())
    if need > cap:
        raise ValueError(
            f"kernel needs ~{need} B of VMEM, more than the {cap} B of a "
            f"{device_kind()} core; plan a smaller tile"
        )
    return int(need)
