"""Accelerator selection.

Analogue of the reference's ``accelerator/real_accelerator.py:45-111``:
``DS_ACCELERATOR`` env override, else auto-detect by probing the JAX backend.
"""
from __future__ import annotations

import os
from typing import Optional

from .abstract_accelerator import DeepSpeedAccelerator

_accelerator: Optional[DeepSpeedAccelerator] = None

SUPPORTED = ("tpu", "cpu")


def _detect_name() -> str:
    name, source = os.environ.get("DS_ACCELERATOR"), "DS_ACCELERATOR"
    if not name:
        import jax

        # a backend that fails to start raises here — it is not reported
        # as "cpu"
        name, source = jax.local_devices()[0].platform, "JAX default platform"
    if name not in SUPPORTED:
        raise ValueError(f"{source} {name!r} not in {SUPPORTED}")
    return name


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is None:
        name = _detect_name()
        if name == "tpu":
            from .tpu_accelerator import TPU_Accelerator

            _accelerator = TPU_Accelerator()
        else:
            from .tpu_accelerator import CPU_Accelerator

            _accelerator = CPU_Accelerator()
    return _accelerator


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator().name() in SUPPORTED
