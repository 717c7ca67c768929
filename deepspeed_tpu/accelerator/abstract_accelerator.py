"""Accelerator abstraction (L0).

TPU-native re-design of the reference's ``accelerator/abstract_accelerator.py:10``
(``DeepSpeedAccelerator`` ABC, ~50 methods).  The torch-specific surface
(Streams/Events, ``torch.cuda`` memory pools) does not map to XLA: streams are
owned by the runtime and synchronization is ``block_until_ready``.  What we keep
is the *seam*: device enumeration/selection, RNG, memory stats, dtype support,
``communication_backend_name`` and the op-builder hooks, so every layer above
talks to ``get_accelerator()`` instead of ``jax.devices()`` directly and the
whole stack runs unchanged on a simulated CPU mesh.
"""
from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional


class DeepSpeedAccelerator(abc.ABC):
    """Device abstraction seam. Reference: accelerator/abstract_accelerator.py:10."""

    def __init__(self):
        self._name: Optional[str] = None
        self._communication_backend_name: Optional[str] = None

    # --- device management (reference abstract_accelerator.py:14-77) ---
    @abc.abstractmethod
    def device_name(self, device_index: Optional[int] = None) -> str:
        ...

    @abc.abstractmethod
    def devices(self) -> List[Any]:
        """All addressable jax devices for this accelerator."""

    @abc.abstractmethod
    def device_count(self) -> int:
        ...

    def global_device_count(self) -> int:
        import jax

        return jax.device_count()

    def process_index(self) -> int:
        import jax

        return jax.process_index()

    def process_count(self) -> int:
        import jax

        return jax.process_count()

    def synchronize(self, tree: Any = None) -> None:
        """XLA analogue of ``torch.cuda.synchronize``."""
        import jax

        if tree is not None:
            jax.block_until_ready(tree)
        else:
            # Dummy computation forces a round-trip through the runtime.
            jax.block_until_ready(jax.numpy.zeros(()))

    # --- RNG (reference abstract_accelerator.py:101-134) ---
    def default_rng(self, seed: int):
        import jax

        return jax.random.PRNGKey(seed)

    # --- memory (reference abstract_accelerator.py:136-168) ---
    @abc.abstractmethod
    def memory_stats(self, device_index: Optional[int] = None) -> Dict[str, int]:
        ...

    def available_memory(self, device_index: Optional[int] = None) -> int:
        stats = self.memory_stats(device_index)
        return stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)

    def total_memory(self, device_index: Optional[int] = None) -> int:
        return self.memory_stats(device_index).get("bytes_limit", 0)

    # --- dtype support (reference abstract_accelerator.py:190-215) ---
    @abc.abstractmethod
    def is_bf16_supported(self) -> bool:
        ...

    @abc.abstractmethod
    def is_fp16_supported(self) -> bool:
        ...

    def supported_dtypes(self) -> List[Any]:
        import jax.numpy as jnp

        dtypes = [jnp.float32]
        if self.is_bf16_supported():
            dtypes.append(jnp.bfloat16)
        if self.is_fp16_supported():
            dtypes.append(jnp.float16)
        return dtypes

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16 if self.is_bf16_supported() else jnp.float32

    # --- comms (reference abstract_accelerator.py:181) ---
    def communication_backend_name(self) -> str:
        assert self._communication_backend_name is not None
        return self._communication_backend_name

    def collective_overlap_options(self) -> Dict[str, str]:
        """Compiler options that make this accelerator's compiler run a
        step's collectives behind the compute beside them.  The engine
        attaches them to a step's compile only where the ZeRO plan has
        collectives to hide (``DeepSpeedEngine.step_compile_options``).
        Empty unless the accelerator's compiler knows such options, so no
        other backend's compile ever sees a key it would refuse."""
        return {}

    # --- profiler ranges (reference abstract_accelerator.py:169-174 nvtx) ---
    def range_push(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name).__enter__()

    def range_pop(self) -> None:  # pragma: no cover - paired with range_push
        pass

    def trace_annotation(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    # --- op builder hooks (reference abstract_accelerator.py:229-244) ---
    @abc.abstractmethod
    def op_builder_dir(self) -> str:
        ...

    def create_op_builder(self, class_name: str):
        builder_class = self.get_op_builder(class_name)
        return None if builder_class is None else builder_class()

    def get_op_builder(self, class_name: str):
        import importlib

        try:
            module = importlib.import_module(self.op_builder_dir())
        except ImportError:
            return None
        return getattr(module, class_name, None)

    # --- identity ---
    def name(self) -> str:
        assert self._name is not None
        return self._name

    def is_available(self) -> bool:
        return self.device_count() > 0
