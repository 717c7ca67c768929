"""A plain float32 reference of the dense decoder block, independent of
``deepspeed_tpu/models/transformer.py``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
kernels, no cache, no scan, no sharding.  It implements exactly the options
the benchmark's configurations use, and refuses any other:

- OPT (``facebook/opt-1.3b``): learned absolute positions added to the token
  embedding, pre-LayerNorm blocks ``x += attn(LN1(x)); x += mlp(LN2(x))``,
  ReLU, biases everywhere, final LayerNorm, LM head tied to the embedding.
  Departure, also listed under ``assumed`` in the configuration file: the
  published checkpoint offsets position ids by 2 rows; here position p reads
  row p.
- GPT-NeoX (``EleutherAI/pythia-1.4b``): rotary embedding on the first
  ``rotary_dim`` dims of each head in the half-split convention, parallel
  residual ``x + attn(LN1(x)) + mlp(LN2(x))``, exact (erf) GELU, biases,
  final LayerNorm, untied LM head.

It reads the parameter tree by the names ``init_params`` gives the leaves
(``layers/wq`` stacked on a leading layer axis, ...): the names are the
interface, the arithmetic is its own.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_SUPPORTED_ACT = ("relu", "gelu_exact")


def _check(cfg):
    bad = []
    if cfg.norm != "layernorm":
        bad.append(f"norm={cfg.norm}")
    if cfg.activation not in _SUPPORTED_ACT:
        bad.append(f"activation={cfg.activation}")
    if cfg.position not in ("learned", "rope"):
        bad.append(f"position={cfg.position}")
    if cfg.position == "rope" and cfg.rope_interleaved:
        bad.append("rope_interleaved")
    if not (cfg.attn_bias and cfg.mlp_bias):
        bad.append("bias-free")
    if cfg.kv_heads != cfg.num_heads:
        bad.append("grouped kv heads")
    if (cfg.post_layernorm or cfg.shared_layernorm or cfg.embed_layernorm
            or not cfg.final_norm or not cfg.causal
            or cfg.attention_layers is not None or cfg.num_experts != 1
            or cfg.attn_softmax_scale is not None or cfg.lm_head_bias):
        bad.append("an option outside the two benchmark configurations")
    if bad:
        raise NotImplementedError(
            "reference.py covers the OPT and GPT-NeoX blocks only: " +
            ", ".join(bad))


def _layernorm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rotary(x, positions, theta, rotary_dim):
    """x [S, H, hd]; rotate the first rotary_dim dims, half-split pairs."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]           # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _block(cfg, lp, x, positions):
    S, d = x.shape
    H, hd = cfg.num_heads, cfg.dims_per_head
    h = _layernorm(x, lp["attn_norm_scale"], lp["attn_norm_bias"], cfg.norm_eps)
    q = (h @ lp["wq"] + lp["bq"]).reshape(S, H, hd)
    k = (h @ lp["wk"] + lp["bk"]).reshape(S, H, hd)
    v = (h @ lp["wv"] + lp["bv"]).reshape(S, H, hd)
    if cfg.position == "rope":
        rd = cfg.rotary_dim or hd
        q = _rotary(q, positions, cfg.rope_theta, rd)
        k = _rotary(k, positions, cfg.rope_theta, rd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    attn = attn.reshape(S, H * hd) @ lp["wo"] + lp["bo"]

    def mlp(y):
        m = y @ lp["w_in"] + lp["b_in"]
        m = (jnp.maximum(m, 0.0) if cfg.activation == "relu"
             else 0.5 * m * (1.0 + jax.lax.erf(m / math.sqrt(2.0))))
        return m @ lp["w_down"] + lp["b_down"]

    if cfg.parallel_residual:
        h2 = _layernorm(x, lp["mlp_norm_scale"], lp["mlp_norm_bias"],
                        cfg.norm_eps)
        return x + attn + mlp(h2)
    x = x + attn
    return x + mlp(_layernorm(x, lp["mlp_norm_scale"], lp["mlp_norm_bias"],
                              cfg.norm_eps))


def reference_logits(cfg, params, tokens):
    """tokens [S] int -> logits [S, V] float32.  One sequence at a time; the
    block is jitted once and called per layer with that layer's weights, so
    nothing of the model's depth is compiled."""
    _check(cfg)
    S = tokens.shape[0]
    positions = jnp.arange(S, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        block = jax.jit(lambda lp, x: _block(cfg, lp, x, positions))
        x = params["embed"].astype(F32)[tokens]
        if cfg.position == "learned":
            x = x + params["pos_embed"].astype(F32)[positions]
        for i in range(cfg.num_layers):
            lp = {k: v[i].astype(F32) for k, v in params["layers"].items()}
            x = block(lp, x)
        x = _layernorm(x, params["final_norm_scale"].astype(F32),
                       params["final_norm_bias"].astype(F32), cfg.norm_eps)
        head = (params["embed"].astype(F32).T if cfg.tie_embeddings
                else params["lm_head"].astype(F32))
        return jnp.dot(x, head)


def rel_err(got, ref) -> float:
    """max |got - ref| over max |ref|, in float32 on the host."""
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / np.abs(ref).max())
