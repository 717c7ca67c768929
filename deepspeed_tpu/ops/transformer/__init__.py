"""Fused BERT-style transformer layer API (reference
``ops/transformer/transformer.py:296`` ``DeepSpeedTransformerLayer`` +
``DeepSpeedTransformerConfig``).

The reference builds this layer from hand-fused CUDA kernels (softmax,
layernorm, dropout, gemm scheduling — csrc/transformer/*.cu); on TPU the
fusion is XLA's job and the flash-attention Pallas kernel covers the one
fusion XLA cannot do.  This module keeps the reference's *API* so BERT-style
training code ports verbatim: a per-layer config, a layer object with
``init``/``apply``, pre-LN or post-LN selection, and the reference's knobs —
where a knob only selects a CUDA implementation detail (``stochastic_mode``,
``normalize_invertible``, ``attn_dropout_checkpoint``, ``gelu_checkpoint``)
it is accepted and recorded, because under XLA the deterministic and
"stochastic" schedules compile to the same program and invertible-LN /
checkpoint tricks are what ``jax.checkpoint`` policies already do.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ...models.transformer import (TransformerConfig, _attend_full,
                                   _block)

__all__ = ["DeepSpeedTransformerConfig", "DeepSpeedTransformerLayer"]


@dataclasses.dataclass
class DeepSpeedTransformerConfig:
    """Reference-shaped config (transformer.py:34)."""

    batch_size: int = -1
    hidden_size: int = -1
    intermediate_size: int = -1
    heads: int = -1
    attn_dropout_ratio: float = 0.0
    hidden_dropout_ratio: float = 0.0
    num_hidden_layers: int = -1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    seed: int = -1
    fp16: bool = False
    pre_layer_norm: bool = True
    # CUDA-implementation knobs, accepted for API parity (see module doc):
    normalize_invertible: bool = False
    gelu_checkpoint: bool = False
    adjust_init_range: bool = True
    attn_dropout_checkpoint: bool = False
    stochastic_mode: bool = False
    return_tuple: bool = False
    training: bool = True

    def to_native(self) -> TransformerConfig:
        if self.intermediate_size <= 0:
            raise ValueError("intermediate_size must be set")
        if self.attn_dropout_ratio != self.hidden_dropout_ratio:
            raise NotImplementedError(
                "separate attention/hidden dropout ratios are not supported "
                "(one dropout knob drives both sites)")
        return TransformerConfig(
            vocab_size=1,  # layer-only: no embedding/head
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_layers=self.num_hidden_layers,
            num_heads=self.heads,
            max_seq_len=1 << 16,
            norm="layernorm", activation="gelu_exact",
            # BERT-style layer: positions live in the embedding, not the
            # block ("learned" => the block applies no rope/alibi), and
            # attention is bidirectional
            position="learned", causal=False,
            post_layernorm=not self.pre_layer_norm,
            attn_bias=True, mlp_bias=True,
            dropout=self.hidden_dropout_ratio,
            norm_eps=self.layer_norm_eps,
            initializer_range=self.initializer_range,
            dtype=jnp.bfloat16 if self.fp16 else jnp.float32,
            remat=self.gelu_checkpoint or self.attn_dropout_checkpoint,
            scan_layers=False)


class DeepSpeedTransformerLayer:
    """One transformer layer with the reference's object surface:
    ``layer = DeepSpeedTransformerLayer(config)``, ``params = layer.init(rng)``,
    ``out = layer.apply(params, hidden_states[, input_mask])``.

    Functional (params are explicit), so the same layer object serves every
    depth — the reference's per-layer ``layer_id`` bookkeeping is not needed.
    """

    def __init__(self, config: DeepSpeedTransformerConfig,
                 initial_weights: Optional[Dict[str, Any]] = None,
                 initial_biases: Optional[Dict[str, Any]] = None):
        self.config = config
        self.native = config.to_native()
        self._initial = (initial_weights, initial_biases)

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        d, f = self.native.hidden_size, self.native.intermediate_size
        hd, nh = self.native.dims_per_head, self.native.num_heads
        std = self.config.initializer_range
        if self.config.adjust_init_range:
            # reference output_std = std / sqrt(2*L) on the residual path
            out_std = std / (2.0 * max(self.config.num_hidden_layers, 1)) ** .5
        else:
            out_std = std
        k = jax.random.split(rng, 8)

        def dense(key, shape, scale=std):
            return jax.random.normal(key, shape, jnp.float32) * scale

        lp = {
            "attn_norm_scale": jnp.ones((d,)),
            "attn_norm_bias": jnp.zeros((d,)),
            "mlp_norm_scale": jnp.ones((d,)),
            "mlp_norm_bias": jnp.zeros((d,)),
            "wq": dense(k[0], (d, nh * hd)), "bq": jnp.zeros((nh * hd,)),
            "wk": dense(k[1], (d, nh * hd)), "bk": jnp.zeros((nh * hd,)),
            "wv": dense(k[2], (d, nh * hd)), "bv": jnp.zeros((nh * hd,)),
            "wo": dense(k[3], (nh * hd, d), out_std), "bo": jnp.zeros((d,)),
            "w_in": dense(k[4], (d, f)), "b_in": jnp.zeros((f,)),
            "w_down": dense(k[5], (f, d), out_std), "b_down": jnp.zeros((d,)),
        }
        iw, ib = self._initial
        if iw:
            lp.update({key: jnp.asarray(v) for key, v in iw.items()})
        if ib:
            lp.update({key: jnp.asarray(v) for key, v in ib.items()})
        return lp

    def apply(self, params: Dict[str, Any], hidden_states: jax.Array,
              input_mask: Optional[jax.Array] = None,
              rng: Optional[jax.Array] = None,
              deterministic: Optional[bool] = None) -> jax.Array:
        if input_mask is not None:
            # reject tracers structurally (concretizing one would surface as
            # a confusing TracerBoolConversionError under jit/vmap); concrete
            # arrays keep the device-side reduce — one scalar transfer
            if isinstance(input_mask, jax.core.Tracer):
                raise NotImplementedError(
                    "input_mask cannot be a traced value: per-token masks "
                    "are not wired into the layer-level API (the BERT "
                    "injection path handles padding); pass None")
            if not bool(jnp.all(input_mask)):
                raise NotImplementedError(
                    "per-token input masks are not wired into the layer-level "
                    "API (the BERT injection path handles padding); pass an "
                    "all-ones mask or None")
        B, S, _ = hidden_states.shape
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
        det = (not self.config.training if deterministic is None
               else deterministic)
        out = _block(
            self.native, params, hidden_states.astype(self.native.dtype),
            positions, rng if rng is not None else jax.random.PRNGKey(
                max(self.config.seed, 0)),
            _attend_full(self.native, positions, "auto"), det)[0]
        return (out,) if self.config.return_tuple else out
