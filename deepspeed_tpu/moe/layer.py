"""Standalone MoE layer (reference ``deepspeed/moe/layer.py:16`` ``MoE``).

The reference wraps a user ``expert`` nn.Module; here the layer is a
functional bundle: ``init(rng)`` creates router+expert params with their
expert-parallel specs, ``apply(params, x, ...)`` runs gate→dispatch→experts→
combine and returns ``(out, aux_loss)`` like the reference's
``MOELayer.forward`` (sharded_moe.py:472).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharded_moe import MoEConfig, moe_ffn


class MoE:
    def __init__(self, hidden_size: int, intermediate_size: Optional[int] = None,
                 num_experts: int = 8, k: int = 2, capacity_factor: float = 1.25,
                 eval_capacity_factor: float = 2.0, min_capacity: int = 8,
                 noisy_gate_policy: Optional[str] = None, drop_tokens: bool = True,
                 use_residual: bool = False, activation: str = "swiglu"):
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.activation = activation
        # residual MoE (reference moe/layer.py:16 use_residual — the R in
        # PR-MoE): a dense MLP branch runs beside the experts and a learned
        # per-token 2-way softmax coefficient mixes the two outputs
        self.use_residual = use_residual
        self.config = MoEConfig(num_experts=num_experts, top_k=k,
                                capacity_factor=capacity_factor,
                                eval_capacity_factor=eval_capacity_factor,
                                min_capacity=min_capacity,
                                noisy_gate_policy=noisy_gate_policy,
                                drop_tokens=drop_tokens)

    def init(self, rng: jax.Array, scale: float = 0.02) -> Dict[str, Any]:
        d, f, E = self.hidden_size, self.intermediate_size, self.config.num_experts
        ks = jax.random.split(rng, 8)
        params = {"router": jax.random.normal(ks[0], (d, E)) * scale}
        if self.activation == "swiglu":
            params["w_gate"] = jax.random.normal(ks[1], (E, d, f)) * scale
            params["w_up"] = jax.random.normal(ks[2], (E, d, f)) * scale
        else:
            params["w_in"] = jax.random.normal(ks[1], (E, d, f)) * scale
        params["w_down"] = jax.random.normal(ks[3], (E, f, d)) * scale
        if self.use_residual:
            if self.activation == "swiglu":
                params["res_w_gate"] = jax.random.normal(ks[4], (d, f)) * scale
                params["res_w_up"] = jax.random.normal(ks[5], (d, f)) * scale
            else:
                params["res_w_in"] = jax.random.normal(ks[4], (d, f)) * scale
            params["res_w_down"] = jax.random.normal(ks[6], (f, d)) * scale
            params["coefficient"] = jax.random.normal(ks[7], (d, 2)) * scale
        return params

    def param_specs(self) -> Dict[str, Any]:
        col = P("expert", None, "model")
        row = P("expert", "model", None)
        specs = {"router": P(None, None), "w_down": row}
        if self.activation == "swiglu":
            specs.update(w_gate=col, w_up=col)
        else:
            specs["w_in"] = col
        if self.use_residual:
            dcol, drow = P(None, "model"), P("model", None)
            if self.activation == "swiglu":
                specs.update(res_w_gate=dcol, res_w_up=dcol)
            else:
                specs["res_w_in"] = dcol
            specs.update(res_w_down=drow, coefficient=P(None, None))
        return specs

    def apply(self, params: Dict[str, Any], x: jnp.ndarray,
              deterministic: bool = True,
              rng: Optional[jax.Array] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        out, aux, _ = moe_ffn(x, params["router"], params, self.config,
                              activation=self.activation,
                              deterministic=deterministic, rng=rng)
        if self.use_residual:
            if self.activation == "swiglu":
                g = x @ params["res_w_gate"].astype(x.dtype)
                u = x @ params["res_w_up"].astype(x.dtype)
                res = (jax.nn.silu(g) * u) @ params["res_w_down"].astype(x.dtype)
            else:
                res = jax.nn.gelu(x @ params["res_w_in"].astype(x.dtype)) \
                    @ params["res_w_down"].astype(x.dtype)
            coef = jax.nn.softmax(
                (x @ params["coefficient"].astype(x.dtype)
                 ).astype(jnp.float32), axis=-1).astype(out.dtype)
            out = out * coef[..., 0:1] + res * coef[..., 1:2]
        return out, aux
