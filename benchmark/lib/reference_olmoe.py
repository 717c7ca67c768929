"""A plain float32 reference of the OLMoE decoder block
(``allenai/OLMoE-1B-7B-0125-Instruct``, ``model_type`` ``olmoe``),
independent of ``deepspeed_tpu/models/transformer.py`` and
``deepspeed_tpu/moe/``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
kernels, no cache, no scan, no sort, no grouped matmul.  The layer, as the
model's description writes it::

    h  = RMSNorm(x)
    q  = RMSNorm_q(h Wq)      k = RMSNorm_k(h Wk)      v = h Wv
         (learned scales over the WHOLE projection, before the head split
          and before the rotary embedding: QK-norm)
    q, k = rotary(q), rotary(k)   on the whole head, half-split pairs
    x += softmax_causal(q k^T / sqrt(head_dim)) v Wo
    h2 = RMSNorm(x)
    p  = softmax_f32(h2 Wr)   over all experts
    the k largest p_e (ties to the lower index) are the weights AS THEY
    ARE, not renormalised; every other expert's weight is 0
    x += sum_e p_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

No token is ever dropped: the experts are a plain loop over all of them
with a weight-or-zero per token.  No biases, untied LM head, final RMSNorm.

It reads the parameter tree by the names ``init_params`` gives the leaves
(``layers/wq`` stacked on a leading layer axis, ``layers/w_gate`` on layer
and expert axes, ...): the names are the interface, the arithmetic is its
own.  Departures from the checkpoint are the configuration file's
(``assumed``): random weights, bfloat16 for the published dtype.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.reference import rel_err  # noqa: F401  (the kind calls it here)

F32 = jnp.float32

# The system's expert layer ALONE against the loop below, one layer's
# weights, a seeded [1, 512, d] activation, max|diff| / max|ref| on the
# layer's own output.  Needed beside the logits check: with N(0, 0.02)
# weights and unnormalised gates of 0.03-0.06 the expert layer adds a few
# percent of the residual stream, so a dropped expert hides under a logits
# tolerance of 0.05 (top-7 routing moved the logits by 0.017-0.022).
# Measured at the published widths on a v5e, bf16 system against this
# float32 loop (PERF.md, PR 26; fifteen seeds, on layer 0 unmasked and on
# the last layer as layer_checks runs it now): 0.0039-0.0058 as shipped;
# top-7 routing 0.094-0.172, renormalised gates 0.75-1.15; this file's own
# loop on weights and activation rounded to float8_e4m3 0.19-0.22 (0.16-
# 0.17 for the activation alone).  0.02 is over three times the rounding
# and a fifth of the nearest of those.
EXPERT_LAYER_REL_TOL = 0.02
# q and k as the attention product takes them (normed, split, rotated), the
# system's projection against this file's: 0.0060-0.0080 as shipped, 0.127-
# 0.159 with the QK-norm left out (q and k then keep the projection's own
# scale, 0.9 of the unit RMS the norm gives them), 0.045-0.046 for this
# file's own in float8_e4m3 (0.027-0.028 for the activation alone).  0.02:
# 2.5 times the rounding, under the next precision down, a sixth of the
# missing norm.
QK_REL_TOL = 0.02


def _check(cfg):
    bad = []
    if cfg.norm != "rmsnorm":
        bad.append(f"norm={cfg.norm}")
    if cfg.activation != "swiglu":
        bad.append(f"activation={cfg.activation}")
    if cfg.position != "rope" or cfg.rope_interleaved or cfg.rotary_dim:
        bad.append("rotary other than whole-head half-split")
    if cfg.attn_bias or cfg.mlp_bias or cfg.lm_head_bias:
        bad.append("biases")
    if cfg.kv_heads != cfg.num_heads:
        bad.append("grouped kv heads")
    if not cfg.qk_norm:
        bad.append("no qk_norm")
    if not isinstance(cfg.num_experts, int) or cfg.num_experts < 2:
        bad.append(f"num_experts={cfg.num_experts}")
    if cfg.moe_norm_topk_prob or cfg.moe_drop_tokens or cfg.moe_use_residual:
        bad.append("renormalised gates, token dropping or a residual expert")
    if (cfg.parallel_residual or cfg.post_layernorm or cfg.shared_layernorm
            or cfg.embed_layernorm or not cfg.final_norm or not cfg.causal
            or cfg.attention_layers is not None or cfg.tie_embeddings
            or cfg.attn_softmax_scale is not None):
        bad.append("an option outside the OLMoE block")
    if bad:
        raise NotImplementedError(
            "reference_olmoe.py covers the OLMoE block only: " + ", ".join(bad))


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotary(x, positions, theta):
    """x [S, H, hd]; the whole head, pairs (i, i + hd/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]           # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def qk(cfg, lp, h, positions):
    """Post-norm activations h [S, d] -> q, k [S, H, hd], normed over the
    whole projection, then split and rotated."""
    S = h.shape[0]
    H, hd = cfg.num_heads, cfg.dims_per_head
    q = _rmsnorm(h @ lp["wq"], lp["q_norm_scale"], cfg.norm_eps)
    k = _rmsnorm(h @ lp["wk"], lp["k_norm_scale"], cfg.norm_eps)
    return (_rotary(q.reshape(S, H, hd), positions, cfg.rope_theta),
            _rotary(k.reshape(S, H, hd), positions, cfg.rope_theta))


def expert_layer(cfg, lp, h2):
    """h2 [S, d] -> sum_e p_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e, the k
    largest p_e of the full softmax as they are."""
    E, k = cfg.num_experts, cfg.moe_top_k
    p = jax.nn.softmax(h2 @ lp["router"], axis=-1)                # [S, E]
    # rank of each expert for each token, ties to the lower index: expert e
    # is outranked by every larger p and by an equal p at a lower index
    lower = jnp.arange(E)[None, :, None] > jnp.arange(E)[None, None, :]
    outranked = ((p[:, None, :] > p[:, :, None])
                 | ((p[:, None, :] == p[:, :, None]) & lower)).sum(-1)
    weight = jnp.where(outranked < k, p, 0.0)                     # [S, E]
    out = jnp.zeros_like(h2)
    for e in range(E):
        g = h2 @ lp["w_gate"][e].astype(F32)
        u = h2 @ lp["w_up"][e].astype(F32)
        out = out + weight[:, e:e + 1] * (
            (g * jax.nn.sigmoid(g) * u) @ lp["w_down"][e].astype(F32))
    return out


def _block(cfg, lp, x, positions):
    S, d = x.shape
    H, hd = cfg.num_heads, cfg.dims_per_head
    h = _rmsnorm(x, lp["attn_norm_scale"], cfg.norm_eps)
    q, k = qk(cfg, lp, h, positions)
    v = (h @ lp["wv"]).reshape(S, H, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(S, H * hd) @ lp["wo"]
    return x + expert_layer(cfg, lp, _rmsnorm(x, lp["mlp_norm_scale"],
                                              cfg.norm_eps))


def _layer(params, i) -> Dict[str, Any]:
    """Layer i's leaves in float32, the three expert stacks as they are
    stored (the loop casts one expert at a time: a whole layer's experts in
    float32 are 1.6 GB at the published widths)."""
    stacks = ("w_gate", "w_up", "w_down")
    return {k: v[i] if k in stacks else v[i].astype(F32)
            for k, v in params["layers"].items()}


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
        for i in range(cfg.num_layers):
            x = block(_layer(params, i), x)
        x = _rmsnorm(x, params["final_norm_scale"].astype(F32), cfg.norm_eps)
        return jnp.dot(x, params["lm_head"].astype(F32))


def layer_checks(cfg, params, seed: int, n_tokens: int = 512,
                 system_cfg=None) -> Dict[str, Dict[str, float]]:
    """The system's layers ALONE against this file's, on the last layer's
    weights and one seeded ``[1, n_tokens, d]`` activation (normal, unit
    variance: what a norm hands on), in the weights' own dtype on the
    system's side: ``{check: {"rel_err", "tol"}}``.  The expert layer on its
    own output, run as the paged forward runs it: the expert leaves the
    whole ``[L*E, ...]`` stack with this layer's experts at their offset,
    and the last eighth of the tokens masked, whose rows must come back
    zero.  q and k as attention takes them.  ``system_cfg`` (a test's
    mutation) is what the system's side runs with, where it is not ``cfg``."""
    from deepspeed_tpu.models import transformer as system

    _check(cfg)
    system_cfg = system_cfg or cfg
    dtype = params["embed"].dtype
    h = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (1, n_tokens, cfg.hidden_size)).astype(np.float32)).astype(dtype)
    positions = jnp.arange(n_tokens, dtype=jnp.int32)
    layer = cfg.num_layers - 1
    live = positions < n_tokens - n_tokens // 8
    lp_ref = _layer(params, layer)
    stacks = ("w_gate", "w_up", "w_down")

    def system_layers(layers, h):
        lp = {k: v.reshape(-1, *v.shape[2:]) if k in stacks else v[layer]
              for k, v in layers.items()}
        moe = system._mlp(system_cfg, lp, h, jax.random.PRNGKey(0),
                          deterministic=True, token_mask=live[None],
                          expert_offset=jnp.int32(layer * cfg.num_experts))[0]
        q, k, _ = system._qkv(system_cfg, lp, h, positions[None])
        return moe[0], q[0], k[0]

    moe, q, k = jax.jit(system_layers)(params["layers"], h)
    with jax.default_matmul_precision("highest"):
        h32 = h[0].astype(F32)
        ref_moe = jax.jit(lambda lp, y: jnp.where(
            live[:, None], expert_layer(cfg, lp, y), 0))(lp_ref, h32)
        ref_q, ref_k = jax.jit(
            lambda lp, y: qk(cfg, lp, y, positions))(lp_ref, h32)
    return {
        "expert_layer": {"rel_err": rel_err(moe, ref_moe),
                         "tol": EXPERT_LAYER_REL_TOL},
        "qk_norm": {"rel_err": max(rel_err(q, ref_q), rel_err(k, ref_k)),
                    "tol": QK_REL_TOL},
    }
