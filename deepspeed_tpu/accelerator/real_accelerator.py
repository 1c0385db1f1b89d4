"""Accelerator selection.

Counterpart of the reference's ``accelerator/real_accelerator.py:45-140``:
``get_accelerator()`` singleton honoring the ``DS_ACCELERATOR`` env var, else
asking JAX for its default backend (``on_tpu`` below).
"""

from __future__ import annotations

import os
from typing import Optional

from .abstract_accelerator import DeepSpeedAccelerator

_accelerator: Optional[DeepSpeedAccelerator] = None


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU: the package's one platform
    test. Kernel dispatch (compiled Pallas vs interpret mode), serving's
    ``attn_impl="auto"`` and accelerator selection all read it. A backend
    that fails to initialise raises here; it is never reported as a CPU."""
    import jax

    return jax.default_backend() == "tpu"


def _detect_platform() -> str:
    override = os.environ.get("DS_ACCELERATOR")
    if override:
        return override.lower()
    return "tpu" if on_tpu() else "cpu"


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is None:
        name = _detect_platform()
        if name == "tpu":
            from .tpu_accelerator import TPU_Accelerator

            _accelerator = TPU_Accelerator()
        elif name == "cpu":
            from .cpu_accelerator import CPU_Accelerator

            _accelerator = CPU_Accelerator()
        else:
            raise ValueError(
                f"DS_ACCELERATOR={name!r} is not supported by the TPU build (expected 'tpu' or 'cpu')"
            )
    return _accelerator


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported() -> bool:
    return _detect_platform() in ("tpu", "cpu")
