"""A plain float32 reference of the MiMo-V2 decoder (``XiaomiMiMo/MiMo-V2.5``
``config.json``, ``model_type`` ``mimo_v2``: the language model), independent
of ``deepspeed_tpu/models/transformer.py`` and ``deepspeed_tpu/moe/``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
kernels, no cache, no pages, no ring, no sort, no grouped matmul, one
sequence.  Layer ``l`` of kind ``k(l)`` (``hybrid_layer_pattern``: full or
window), RMSNorm, no biases::

    h  = RMSNorm(x)
    q  = h Wq  -> Hq heads x 192      k = h Wk -> Hkv x 192
    v  = 0.707 (h Wv) -> Hkv x 128    Hkv = 4 (full) or 8 (window)
    rotary on the leading 64 dims of q and k, pairs (i, i + 32),
         theta 1e7 (full) or 1e4 (window); the other 128 pass through
    s_ij = q_i k_j / sqrt(192),  j <= i,  and on a window layer i - j <= 127
    window:  p_ij = exp(s_ij - m_i) / (exp(b_h - m_i) + sum_j exp(s_ij - m_i))
             (b_h: one learned logit a query head; m_i the row's maximum
              over its scores and b_h; the sink takes probability and
              gives no value)
    full:    the plain softmax
    x += (sum_j p_ij v_j) Wo        h2 = RMSNorm(x)
    layer 0:   x += W_down (silu(h2 W_gate) * (h2 W_up))
    others:    z = h2 W_r (float32, 256 wide)     sigma = sigmoid(z)
               the 8 experts with the largest sigma_e + beta_e (ties to the
               lower index; beta enters the choice and not the gate)
               g_e = sigma_e / sum_chosen sigma
               x += sum over the chosen e THAT ARE HELD of
                    g_e W_down,e (silu(h2 W_gate,e) * (h2 W_up,e))

``held = (first, count)`` is one chip's share of the experts: the choice and
the gates are over all 256, and what an absent expert would add is left out
(all of them are held when the configuration has no share).  Final RMSNorm,
untied head.

It reads the parameter tree by the names ``init_params`` gives the leaves
(``layers/<kind>_<dense|moe>/wq`` stacked over the group's layers, in the
order the layers have in ``layer_pattern``): the names are the interface, the
arithmetic is its own.  Departures from the checkpoint are the configuration
file's (``assumed``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# How many tokens of a block are left out of its reading, the worst first:
# one in 200.  Top-8-of-256 routing is a discrete choice: where the 8th and
# 9th scores lie closer than bfloat16 activations resolve them, the system
# and a float32 reference choose different experts, and that one token's
# logits move by a whole expert's share of the stream (3-5% of max|logit|
# here, where rounding alone moves them by 1.1%: PERF.md, PR 30).  In a
# 3,000-token prompt a few dozen tokens flip and the largest of them would
# be the block's reading whatever the arithmetic.  The routing itself is
# held by ``layer_checks`` (both sides route the same activation there, so
# nothing flips); a fault that moves one token in 128 (a page's edge) still
# shows.  A single token (a decode step) is read as it is.
FLIP_SHARE = 200
# max|logit| of a block in units of its root mean square: 5.5 over 3,000 x
# 19,072 Gaussian logits, 4.1 over one token's 19,072.  Every reading is
# taken against 5.5 x rms, so that a decode step and a prompt are read on
# one scale.
PEAK_OVER_RMS = 5.5


def rel_err(got, want) -> float:
    """The largest |got - want| of a token's logits, over ``PEAK_OVER_RMS``
    x the reference's root mean square: of one token as it is, of a block
    ``[S, V]`` the largest after the worst ``S // FLIP_SHARE`` tokens (see
    ``FLIP_SHARE``).  Any other shape: max|diff| / max|want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if want.ndim > 2 or got.shape != want.shape:
        return float(np.abs(got - want).max() / np.abs(want).max())
    scale = PEAK_OVER_RMS * float(np.sqrt(np.mean(want * want)))
    per_token = np.sort(np.abs(got - want).reshape(-1, want.shape[-1]).max(-1))
    return float(per_token[len(per_token) - 1 - len(per_token) // FLIP_SHARE]
                 / scale)


def layer_rel_err(got, want) -> float:
    """max|diff| / max|ref| on a sublayer's own output."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())

# Single layers of the system against this file's, each group's last layer,
# a seeded [1, 512, d] activation of unit variance, max|diff| / max|ref| on
# the sublayer's own output.  Needed beside the logits check: the sink, the
# window's edge or one expert of eight move the logits by less than routing
# flips do.  Each limit lies between two readings at the published widths on
# a v5e (my chip runs, PR 30; PERF.md §6): the largest the shipped bfloat16
# system gives over eight seeds, and this file's own arithmetic with
# weights and activation rounded to float8_e4m3 (two seeds), with every
# mutation a test makes (``mutate``) outside it:
#   window layer's attention (q, k, v, two thetas, the window in chunks, the
#   sink, the value scale, Wo): as shipped 0.0038-0.0060 (sixteen seeds); in
#   float8_e4m3 0.097-0.138; no sink 0.52-0.74, window 256 0.30-0.38, value
#   scale 1 0.29, the full layers' theta 0.33-0.39.
WINDOW_ATTN_REL_TOL = 0.02
#   full layer's attention (the larger of the leading dense layer's and the
#   expert layers'): as shipped 0.0034-0.0047; in float8_e4m3 0.070-0.083;
#   value scale 1 0.29, the window layers' theta 0.28-0.34.
FULL_ATTN_REL_TOL = 0.02
#   the expert layer alone, this share's part of the sum (the larger of the
#   window and the full group's): as shipped 0.0045-0.0062; in float8_e4m3
#   0.71-0.79; the bias added to the gate 0.056-0.093 (the nearest), top-7
#   0.54-0.64, softmax for sigmoid 0.80-0.86, no renormalisation 0.86-0.87,
#   the held range shifted by one expert 1.44-1.61.  0.02 is 3.2 x the
#   largest rounding and under 0.36 of the nearest mutation.
EXPERT_LAYER_REL_TOL = 0.02
# The logits (``rel_err`` above, against the serve-backlog kind's 0.05), a
# 3,000-token prompt and 16 decode steps through both pools: as shipped
# 0.0347-0.0386 and 0.013-0.039 over thirty runs; this file's own forward
# in float8_e4m3 0.191-0.195 and 0.17-0.21.  Without the flips left out a
# prompt read 0.037-0.047 by its largest token (five seeds).


def spec(cfg, **mutate) -> Dict[str, Any]:
    """What the equations take from the configuration, as plain values; a
    test's mutation overrides one of them."""
    s = {
        "window": cfg.window_size,
        "theta": {"full": cfg.rope_theta,
                  "window": cfg.window_rope_theta or cfg.rope_theta},
        "rotary": cfg.rotary_dim or cfg.dims_per_head,
        "value_scale": cfg.attn_value_scale,
        "sink": cfg.window_attn_sink,
        "top_k": cfg.moe_top_k,
        "score": cfg.moe_score_func,
        "norm_topk": cfg.moe_norm_topk_prob,
        "bias_in_gate": False,
        "held": (cfg.moe_expert_first,
                 cfg.moe_experts_held or cfg.num_experts),
        "eps": cfg.norm_eps,
        "heads": cfg.num_heads,
        "hd": cfg.dims_per_head,
        "vd": cfg.v_head_dim or cfg.dims_per_head,
    }
    s.update(mutate)
    return s


def _check(cfg):
    bad = []
    if cfg.layer_pattern is None:
        bad.append("no layer_pattern")
    if cfg.norm != "rmsnorm" or cfg.activation != "swiglu":
        bad.append(f"norm={cfg.norm}, activation={cfg.activation}")
    if cfg.position != "rope" or cfg.rope_interleaved:
        bad.append("rotary other than half-split pairs")
    if (cfg.attn_bias or cfg.mlp_bias or cfg.lm_head_bias or cfg.qk_norm
            or cfg.parallel_residual or cfg.post_layernorm
            or cfg.shared_layernorm or cfg.embed_layernorm
            or not cfg.final_norm or not cfg.causal or cfg.tie_embeddings
            or cfg.attn_softmax_scale is not None or cfg.moe_drop_tokens
            or cfg.moe_use_residual or cfg.attention_layers is not None):
        bad.append("an option outside the MiMo-V2 block")
    if bad:
        raise NotImplementedError(
            "reference_mimo_v2.py covers the MiMo-V2 block only: "
            + ", ".join(bad))


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, positions, theta: float, rotary_dims: int):
    """x [S, H, hd]: the leading ``rotary_dims`` dims rotated in pairs
    (i, i + rotary_dims/2), the rest passed through."""
    half = rotary_dims // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]           # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary_dims]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rotary_dims:]], -1)


def attention(s, kind: str, lp, h, positions):
    """Post-norm activations h [S, d] -> the attention sublayer's output
    [S, d] (projections, rotary, the kind's mask and sink, Wo)."""
    S = h.shape[0]
    H, hd, vd = s["heads"], s["hd"], s["vd"]
    q = (h @ lp["wq"]).reshape(S, H, hd)
    Hkv = lp["wk"].shape[-1] // hd
    k = (h @ lp["wk"]).reshape(S, Hkv, hd)
    v = s["value_scale"] * (h @ lp["wv"]).reshape(S, Hkv, vd)
    q = rotary(q, positions, s["theta"][kind], s["rotary"])
    k = rotary(k, positions, s["theta"][kind], s["rotary"])
    back = positions[:, None] - positions[None, :]                # i - j
    ok = back >= 0
    if kind == "window":
        ok = ok & (back < s["window"])
    G = H // Hkv
    out = []
    for g in range(Hkv):        # a KV head's query heads at a time: the
        qs = q[:, g * G:(g + 1) * G]     # [S, S] scores of all 64 do not fit
        sc = jnp.einsum("qhd,kd->hqk", qs, k[:, g]) / math.sqrt(hd)
        sc = jnp.where(ok[None], sc, -jnp.inf)
        m = sc.max(-1, keepdims=True)
        if kind == "window" and s["sink"]:
            b = lp["attn_sink"][g * G:(g + 1) * G][:, None, None]
            m = jnp.maximum(m, b)
            den = jnp.exp(b - m) + jnp.exp(sc - m).sum(-1, keepdims=True)
        else:
            den = jnp.exp(sc - m).sum(-1, keepdims=True)
        out.append(jnp.einsum("hqk,kd->qhd", jnp.exp(sc - m) / den, v[:, g]))
    return jnp.concatenate(out, axis=1).reshape(S, H * vd) @ lp["wo"]


def expert_weights(s, lp, h2):
    """h2 [S, d] -> the gate of every expert for every token [S, E]: the
    chosen experts' gates, 0 for the rest."""
    z = h2 @ lp["router"]
    E = z.shape[-1]
    score = (jax.nn.sigmoid(z) if s["score"] == "sigmoid"
             else jax.nn.softmax(z, axis=-1))
    choose = score + lp["router_bias"] if "router_bias" in lp else score
    # rank of each expert for each token, ties to the lower index
    lower = jnp.arange(E)[None, :, None] > jnp.arange(E)[None, None, :]
    outranked = ((choose[:, None, :] > choose[:, :, None])
                 | ((choose[:, None, :] == choose[:, :, None]) & lower)
                 ).sum(-1)
    gate = choose if s["bias_in_gate"] else score
    gate = jnp.where(outranked < s["top_k"], gate, 0.0)
    if s["norm_topk"]:
        gate = gate / gate.sum(-1, keepdims=True)
    return gate


def expert_layer(s, lp, h2):
    """h2 [S, d] -> the held experts' part of sum_e g_e expert_e(h2)."""
    gate = expert_weights(s, lp, h2)
    first, count = s["held"]
    out = jnp.zeros_like(h2)
    for e in range(count):      # lp's stacks hold experts first .. first+count
        g = h2 @ lp["w_gate"][e].astype(F32)
        u = h2 @ lp["w_up"][e].astype(F32)
        out = out + gate[:, first + e:first + e + 1] * (
            (g * jax.nn.sigmoid(g) * u) @ lp["w_down"][e].astype(F32))
    return out


def dense_mlp(lp, h2):
    g = h2 @ lp["w_gate"]
    return (g * jax.nn.sigmoid(g) * (h2 @ lp["w_up"])) @ lp["w_down"]


def _block(s, kind, dense, lp, x, positions):
    x = x + attention(s, kind, lp, _rmsnorm(x, lp["attn_norm_scale"],
                                            s["eps"]), positions)
    h2 = _rmsnorm(x, lp["mlp_norm_scale"], s["eps"])
    return x + (dense_mlp(lp, h2) if dense else expert_layer(s, lp, h2))


def layers(cfg):
    """``(group, index in the group, kind, dense)`` for each layer in the
    published order, from the first ``num_layers`` entries of
    ``layer_pattern`` and ``dense_layers`` alone."""
    seen: Dict[str, int] = {}
    out = []
    for i, kind in enumerate(cfg.layer_pattern[:cfg.num_layers]):
        dense = i < cfg.dense_layers
        group = f"{kind}_{'dense' if dense else 'moe'}"
        out.append((group, seen.get(group, 0), kind, dense))
        seen[group] = seen.get(group, 0) + 1
    return out


def _layer(params, group: str, i: int, round_to=None) -> Dict[str, Any]:
    """One layer's leaves in float32, an expert layer's three stacks as
    they are stored (the loop casts one expert at a time).  ``round_to``: a
    dtype every weight is rounded through first (the next precision down)."""
    def f32(a):
        return (a.astype(round_to) if round_to is not None else a).astype(F32)

    stacked = "router" in params["layers"][group]
    return {k: (v[i] if round_to is None else v[i].astype(round_to))
            if stacked and k in ("w_gate", "w_up", "w_down") else f32(v[i])
            for k, v in params["layers"][group].items()}


def reference_logits(cfg, params, tokens, held=None, round_to=None, **mutate):
    """tokens [S] int -> logits [S, V] float32.  One sequence; each layer is
    jitted and run with its own weights, a layer at a time from the leaves
    as they are stored, so the float32 copy of one layer is all that is
    held beside them.  ``held``: the share of the experts ``params`` hold,
    where it is not the configuration's.  ``round_to``: a dtype every weight
    and every layer's input is rounded through (the next precision down)."""
    _check(cfg)
    s = spec(cfg, **({"held": held} if held is not None else {}), **mutate)
    S = tokens.shape[0]
    positions = jnp.arange(S, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        blocks: Dict[Any, Any] = {}
        for group, i, kind, dense in layers(cfg):
            if group not in blocks:
                blocks[group] = jax.jit(
                    lambda lp, x, kind=kind, dense=dense:
                    _block(s, kind, dense, lp, x, positions))
            if round_to is not None:
                x = x.astype(round_to).astype(F32)
            x = blocks[group](_layer(params, group, i, round_to), x)
        x = _rmsnorm(x, params["final_norm_scale"].astype(F32), cfg.norm_eps)
        head = params["lm_head"]
        if round_to is not None:
            x, head = x.astype(round_to).astype(F32), head.astype(round_to)
        return jnp.dot(x, head.astype(F32))


def layer_checks(cfg, params, seed: int, n_tokens: int = 512,
                 mutate: Optional[Dict[str, Any]] = None, round_to=None
                 ) -> Dict[str, Dict[str, float]]:
    """The system's sublayers ALONE against this file's, on the last layer
    of each group (the larger reading where two groups share a check) and
    one seeded ``[1, n_tokens, d]`` activation (normal,
    unit variance: what a norm hands on), in the weights' own dtype on the
    system's side: ``{check: {"rel_err", "tol"}}``.

    ``window_attention`` / ``full_attention``: the attention sublayer as a
    prompt's prefill runs it (projections, two thetas, the window in chunks
    and the sink; the masked product), through Wo.  ``expert_layer``: run as
    the paged forward runs it: the expert leaves the group's whole ``[n*E,
    ...]`` stack with this layer's experts at their offset, the last eighth
    of the tokens masked, whose rows must come back zero.

    ``mutate`` (a test's) changes this file's side (:func:`spec`);
    ``round_to`` rounds this file's weights and activation through a
    narrower dtype.  Either must push a check past its limit."""
    from deepspeed_tpu.models import transformer as system

    _check(cfg)
    s = spec(cfg, **(mutate or {}))
    dtype = params["embed"].dtype
    h = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (1, n_tokens, cfg.hidden_size)).astype(np.float32)).astype(dtype)
    positions = jnp.arange(n_tokens, dtype=jnp.int32)
    live = positions < n_tokens - n_tokens // 8
    groups = system.layer_groups(cfg)
    h_ref = (h[0].astype(round_to) if round_to is not None
             else h[0]).astype(F32)
    out: Dict[str, Dict[str, float]] = {}

    def record(name, got, want, tol):
        # the larger of the groups' readings where two groups share a check
        err = max(layer_rel_err(got, want), out.get(name, {}).get("rel_err", 0.0))
        out[name] = {"rel_err": err, "tol": tol}

    for group, i in {group: i for group, i, _, _ in layers(cfg)}.items():
        kind, dense = group.split("_")[0], group.endswith("_dense")
        g = groups[group][0]
        leaves = params["layers"][group]
        lp_ref = _layer(params, group, i, round_to)

        def system_attention(leaves, h):
            lp = {k: v[i] for k, v in leaves.items()
                  if k not in system._EXPERT_LEAVES or dense}
            q, k, v = system._qkv(g, lp, h, positions[None])
            if kind == "window":
                a = system._attention_window_block(
                    g, q, k, v, positions[None], cfg.window_size,
                    lp.get("attn_sink"))
            else:
                a = system._attention(g, q, k, v, positions[None], "xla",
                                      custom_positions=True)
            return system._attn_out(g, lp, a)[0]

        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda lp, y: attention(
                s, kind, lp, y, positions))(lp_ref, h_ref)
        record(f"{kind}_attention", jax.jit(system_attention)(leaves, h),
               want, WINDOW_ATTN_REL_TOL if kind == "window"
               else FULL_ATTN_REL_TOL)
        if dense:
            continue
        held = g.moe_experts_held or g.num_experts

        def system_experts(leaves, h):
            lp = {k: v.reshape(-1, *v.shape[2:])
                  if k in system._EXPERT_LEAVES else v[i]
                  for k, v in leaves.items()}
            return system._mlp(g, lp, h, jax.random.PRNGKey(0),
                               deterministic=True, token_mask=live[None],
                               expert_offset=jnp.int32(i * held))[0][0]

        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda lp, y: jnp.where(
                live[:, None], expert_layer(s, lp, y), 0))(lp_ref, h_ref)
        record("expert_layer", jax.jit(system_experts)(leaves, h), want,
               EXPERT_LAYER_REL_TOL)
    return out
