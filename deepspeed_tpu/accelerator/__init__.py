from .abstract_accelerator import DeepSpeedAccelerator
from .real_accelerator import (
    get_accelerator,
    is_current_accelerator_supported,
    on_tpu,
    set_accelerator,
)

__all__ = [
    "DeepSpeedAccelerator",
    "get_accelerator",
    "set_accelerator",
    "is_current_accelerator_supported",
    "on_tpu",
]
