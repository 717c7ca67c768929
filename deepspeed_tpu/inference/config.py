"""Inference config (reference ``deepspeed/inference/config.py:127``
``DeepSpeedInferenceConfig``)."""
from __future__ import annotations

from typing import Any, Dict, Optional

from pydantic import Field

from ..runtime.config_utils import DeepSpeedConfigModel


class DeepSpeedTPConfig(DeepSpeedConfigModel):
    """tensor_parallel block (reference config.py:33)."""

    enabled: bool = True
    tp_size: int = Field(1, ge=1)


class DeepSpeedMoEConfig(DeepSpeedConfigModel):
    enabled: bool = True
    ep_size: int = Field(1, ge=1)
    moe_experts: list = Field(default_factory=lambda: [1])


class QuantizationConfig(DeepSpeedConfigModel):
    enabled: bool = False
    num_bits: int = 8


class DeepSpeedInferenceConfig(DeepSpeedConfigModel):
    """Reference inference/config.py:127 — the knobs that survive the TPU
    redesign.  ``replace_with_kernel_inject`` maps to swapping HF/flax modules
    for Pallas-fused blocks (module_inject); cuda-graph capture maps to jit
    AOT compilation (always on under jit, so the flag is accepted and
    ignored)."""

    dtype: str = "bfloat16"  # reference default fp16; bf16 is TPU-native
    tensor_parallel: DeepSpeedTPConfig = Field(default_factory=DeepSpeedTPConfig, alias="tp")
    moe: DeepSpeedMoEConfig = Field(default_factory=DeepSpeedMoEConfig)
    quant: QuantizationConfig = Field(default_factory=QuantizationConfig)
    checkpoint: Optional[str] = None
    replace_with_kernel_inject: bool = False
    injection_policy: Optional[Dict[Any, Any]] = None
    max_out_tokens: int = Field(1024, ge=1)
    min_out_tokens: int = Field(1, ge=1)
    max_tokens: int = 1024
    enable_cuda_graph: bool = False  # accepted for parity; jit IS the graph
    replace_method: str = "auto"
    zero: Dict[str, Any] = Field(default_factory=dict)
    triangular_masking: bool = True
    return_tuple: bool = True

    @property
    def jnp_dtype(self):
        import jax.numpy as jnp

        return {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16, "float16": jnp.float16,
                "fp16": jnp.float16, "half": jnp.float16, "float32": jnp.float32,
                "fp32": jnp.float32, "int8": jnp.int8}[str(self.dtype)]

    @property
    def weights_quantized(self) -> bool:
        """dtype "int8" means WEIGHT-ONLY quantization (reference
        ZeRO-Inference ``init_inference(dtype=torch.int8)``), as does the
        explicit quant block — one property so loader and engine agree."""
        return bool(self.quant.enabled or str(self.dtype) == "int8")

    @property
    def compute_jnp_dtype(self):
        """Activation/dequant dtype: int8 storage computes in bf16; any
        other configured dtype is honored (quant.enabled + fp32 runs fp32)."""
        import jax.numpy as jnp

        d = self.jnp_dtype
        return jnp.bfloat16 if d == jnp.int8 else d
