"""Hybrid engine: one weight set, training AND fast generation (RLHF).

Parity target: reference ``runtime/hybrid_engine.py:32``
(``DeepSpeedHybridEngine`` — the DeepSpeed-Chat actor engine whose
``generate()`` runs with inference kernels/containers over the SAME weights
ZeRO is training, gathering/partitioning params on each train↔eval flip).

TPU-native redesign: the reference's hard part — swapping torch modules for
inference containers and un/re-partitioning ZeRO shards around every
generate — disappears here.  The training engine already maintains a
compute-precision (bf16) param view next to the fp32 masters, and the
inference engine's compiled generate program takes params as an ARGUMENT.
So hybrid = hand the live training view to the KV-cache decode program:
zero copies, zero re-partitioning, no mode flip; XLA reshards between the
training and decode layouts automatically if they differ.
"""
from __future__ import annotations

import time
from typing import Any, Optional

from ..utils.logging import log_dist


class DeepSpeedHybridEngine:
    """Wraps a training engine with a generation path over the live weights.

    ``model`` must expose ``apply_cached`` (the KV-cache step — e.g.
    ``deepspeed_tpu.models.CausalLM``); defaults to the training engine's
    model.  Typical RLHF actor loop::

        hybrid = DeepSpeedHybridEngine(engine)
        rollout = hybrid.generate(prompts, max_new_tokens=128)
        ...score rollout, build the PPO batch...
        hybrid.train_batch(batch=ppo_batch)
    """

    def __init__(self, engine, model: Any = None, inference_config=None):
        from ..inference.engine import InferenceEngine
        from ..inference.config import DeepSpeedInferenceConfig

        self.engine = engine
        # generation state (KV caches, a rollout engine's page pool) shares
        # the device with the trainer and is in no fused step's program, so
        # the step's own bytes do not say what fits: the engine resolves no
        # checkpoint policy here ("nothing_saveable" unless the config names
        # one; docs/TUNING.md "Remat")
        engine._remat_auto = False
        model = model or engine.model
        if model is None or not hasattr(model, "apply_cached"):
            raise ValueError(
                "hybrid engine needs a KV-cache-capable model (apply_cached); "
                "pass the CausalLM adapter the training engine was built with")
        self.model = model
        # LoRA actor (runtime/lora.py LoRAModel): generation fuses the
        # adapters into the base weights ONCE per call instead of per decode
        # step (reference fuse_lora_weight/unfuse_lora_weight,
        # hybrid_engine.py:138-160)
        self._lora = model if hasattr(model, "fused") and \
            hasattr(model, "base_model") else None
        self._gen_model = self._lora.base_model if self._lora else model
        self._fuse_jit = None
        self._fused_params = None
        self._fused_at_step = None
        # compute_dtype may be a dtype CLASS (jnp.bfloat16) or a dtype
        # INSTANCE (np.dtype("bfloat16")) — `.__name__` only exists on the
        # class and crashed on instances; jnp.dtype() normalizes both
        import jax.numpy as jnp

        dtype_name = jnp.dtype(engine.compute_dtype).name
        cfg = inference_config or DeepSpeedInferenceConfig(
            dtype={"bfloat16": "bf16", "float16": "fp16"}.get(dtype_name,
                                                              "fp32"))
        # params=None: generation always reads the LIVE training view
        self._infer = InferenceEngine(self._gen_model, config=cfg, params=None,
                                      apply_fn=self._gen_model.apply_fn,
                                      mesh=engine.mesh)
        self._generate_calls = 0
        self._generate_time = 0.0

    # -- LoRA fuse/unfuse (reference hybrid_engine.py:138-160) --
    def fuse_lora_weight(self):
        """Materialize base + A@B·scale for generation.  Pure function of
        the live adapter tree — the base weights are never mutated, so
        'unfuse' is just dropping this cache."""
        if self._lora is None:
            return  # API parity no-op (reference skips without LoRA too)
        import jax

        if self._fuse_jit is None:
            self._fuse_jit = jax.jit(self._lora.fused)
        self._fused_params = self._fuse_jit(self.engine.state.params)
        self._fused_at_step = self.engine.global_steps

    def unfuse_lora_weight(self):
        self._fused_params = None
        self._fused_at_step = None

    def _generation_params(self):
        if self._lora is None:
            return self.engine.state.params
        if self._fused_params is None or \
                self._fused_at_step != self.engine.global_steps:
            self.fuse_lora_weight()   # auto-refresh after training flips
        return self._fused_params

    # -- generation over the live weights (reference generate():238) --
    def generate(self, input_ids, **kwargs) -> Any:
        t0 = time.perf_counter()
        out = self._infer.generate(input_ids, model=self._gen_model,
                                   params=self._generation_params(), **kwargs)
        self._generate_time += time.perf_counter() - t0
        self._generate_calls += 1
        return out

    # -- batched rollouts through the serving stack (docs/HYBRID.md) --
    def rollout_engine(self, **kwargs):
        """A :class:`~..rollout.RolloutEngine` sharing this hybrid
        engine's live weights, LoRA fuse cache and model: rollouts run
        through the continuous-batching paged serving engine (per-slot
        sampling lanes, warm-restart supervision, weight-epoch KV
        invalidation) instead of sequential :meth:`generate` — the
        production RLHF actor path.  Kwargs configure the underlying
        ``ServingEngine`` (``b_slots``, ``max_model_len``, ...)."""
        from ..rollout import RolloutEngine

        return RolloutEngine(self, **kwargs)

    # -- training passthrough --
    def train_batch(self, *args, **kwargs):
        return self.engine.train_batch(*args, **kwargs)

    def eval_batch(self, *args, **kwargs):
        return self.engine.eval_batch(*args, **kwargs)

    def save_checkpoint(self, *args, **kwargs):
        return self.engine.save_checkpoint(*args, **kwargs)

    def load_checkpoint(self, *args, **kwargs):
        return self.engine.load_checkpoint(*args, **kwargs)

    # reference mode flips are no-ops here (no container swap needed), kept
    # for API parity with DeepSpeed-Chat call sites
    def eval(self):
        return self

    def train(self, mode: bool = True):
        return self

    @property
    def module(self):
        return self.engine.module

    def report_generate_latency(self) -> Optional[float]:
        """Mean generate() wall-clock (reference _generate latency stats)."""
        if not self._generate_calls:
            return None
        mean = self._generate_time / self._generate_calls
        log_dist(f"hybrid engine: {self._generate_calls} generate calls, "
                 f"mean {mean * 1e3:.1f} ms", ranks=[0])
        return mean
