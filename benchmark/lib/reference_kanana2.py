"""A plain float32 reference of the Kanana-2 decoder
(``kakaocorp/kanana-2-30b-a3b-instruct-2601`` ``config.json``, ``model_type``
``deepseek_v3``), independent of ``deepspeed_tpu/models/transformer.py`` and
``deepspeed_tpu/moe/``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
kernels, no cache, no pages, no absorption, no sort, no grouped matmul, one
sequence.  Every layer, RMSNorm (eps 1e-6), no biases::

    h  = RMSNorm(x)
    q  = h W_q   -> 32 heads x 192 = [q_nope (128) ; q_pe (64)]
    a  = h W_kva -> 576 = [c (512) ; k_pe (64)]
    c  = RMSNorm_512(c)              (the kv_a norm, a scale of its own)
    rotary on q_pe and on k_pe only: 64 dims, theta 1e6, adjacent pairs
         (2i, 2i + 1); k_pe is ONE row, shared by every head
    kv = c W_kvb -> 32 heads x 256 = [k_nope_h (128) ; v_h (128)]
    s_h,ij = (q_nope_h,i k_nope_h,j + q_pe_h,i k_pe_j) / sqrt(192),  j <= i
    x += concat_h(sum_j softmax_j(s_h,ij) v_h,j) W_o        h2 = RMSNorm(x)
    layer 0:   x += W_down (silu(h2 W_gate) * (h2 W_up))       (width 6,144)
    others:    z = h2 W_r (float32, 128 wide)     sigma = sigmoid(z)
               the 6 experts with the largest sigma_e + beta_e (ties to the
               lower index; beta enters the choice and not the gate)
               g_e = 2.448 sigma_e / sum_chosen sigma
               x += sum over the chosen e THAT ARE HELD of
                    g_e W_down,e (silu(h2 W_gate,e) * (h2 W_up,e))
                  + S_down (silu(h2 S_gate) * (h2 S_up))
               (S: the 2 shared experts as one MLP of width 2 x 768, every
                token's, whatever the share)

``held = (first, count)`` is one chip's share of the routed experts: the
choice and the gates are over all 128, and what an absent expert would add
is left out.  Final RMSNorm, untied head.  This is the expanded mathematics
only: the system's second path (latent rows read back with ``W_kvb``
absorbed into the query and the output) has to give the same numbers.

It reads the parameter tree by the names ``init_params`` gives the leaves
(``layers/full_dense/...`` and ``layers/full_moe/...`` stacked over the
group's layers): the names are the interface, the arithmetic is its own.
Departures from the checkpoint are the configuration file's (``assumed``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# How the logits are read.  Top-6-of-128 routing is a discrete choice: where
# the 6th and 7th scores lie closer than bfloat16 activations resolve them
# the system and a float32 reference choose different experts, and that one
# token's logits move by an expert's share of the stream, whatever the
# arithmetic.  At the published widths on a v5e (my chip runs, PR 32: two
# seeds, 3,000 prompt tokens and 16 decode tokens each, every token's
# max|diff| over 5.5 x the reference's root mean square, which is what
# max|ref| is over a prompt) the shipped system reads **0.008-0.011 on a
# token no flip reached** (the median), **0.02-0.05 on one a flip did**
# (one token in ten reads over 0.03: 23 expert layers stand where MiMo's
# cut had 6) and 0.060 and 0.068 on the worst token of 3,000; this file's
# own forward in float8_e4m3 reads **0.13 on its best token of 6,032**,
# 0.17 at the median and 0.24 on its worst.  Nothing the system computes
# wrongly lies between 0.07 and 0.13, and the serve-backlog kind's limit is
# 0.05 of whatever ``rel_err`` returns: so a reading is taken against 5.5 x
# ``FLIP_ROOM`` x rms with ``FLIP_ROOM`` = sqrt(23 / 6) = 1.96 (a flipped
# expert's error adds up over the expert layers as a random walk does; the
# kind's 0.05 was shown to clear the flips of a cut with 6).  The factor
# was chosen after those first readings and is a model of how flips add,
# not a measurement; what is measured is where the limit it gives lies:
# 0.098 of max|ref| per token, between the 0.068 of the shipped system's
# worst token and the 0.13 of the float8 reference's best, and through
# this function the shipped system reads half the limit and the float8
# reference twice it (below).
FLIP_ROOM = math.sqrt(23 / 6)
# A block is read by its largest token after the worst one in 200
# (``reference_mimo_v2``'s rule: a fault on one token in 128, a page's edge,
# still shows): as shipped 0.048 and 0.049 before the scale, 0.0232-0.0262
# after it (seventeen seeds); this file's own forward in float8_e4m3, read
# by this function on the parity's token draw, 0.107-0.110 (three seeds).
# A single token (a decode step) is read as it is: 0.033-0.045 before the
# scale on the worst of 16, 0.0096-0.0231 after it; in float8_e4m3
# 0.097-0.106.  The routing itself is held by ``layer_checks``, where both
# sides route one activation and nothing flips.
FLIP_SHARE = 200
# max|logit| of a block in units of its root mean square (5.5 over a prompt
# of Gaussian logits, 4.1 over one token's): every reading is taken on the
# one scale, so that a decode step and a prompt are read alike.
PEAK_OVER_RMS = 5.5


def rel_err(got, want) -> float:
    """The largest |got - want| of a token's logits, over ``PEAK_OVER_RMS``
    x ``FLIP_ROOM`` x the reference's root mean square: of one token as it
    is, of a block
    ``[S, V]`` the largest after the worst ``S // FLIP_SHARE`` tokens (see
    ``FLIP_SHARE``).  Any other shape: max|diff| / max|want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if want.ndim > 2 or got.shape != want.shape:
        return float(np.abs(got - want).max() / np.abs(want).max())
    scale = PEAK_OVER_RMS * FLIP_ROOM * float(np.sqrt(np.mean(want * want)))
    per_token = np.sort(np.abs(got - want).reshape(-1, want.shape[-1]).max(-1))
    return float(per_token[len(per_token) - 1 - len(per_token) // FLIP_SHARE]
                 / scale)


def layer_rel_err(got, want) -> float:
    """max|diff| / max|ref| on a sublayer's own output."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


# Single layers of the system against this file's, the expert group's last
# layer, a seeded [1, 1024, d] activation of unit variance, max|diff| /
# max|ref| on the sublayer's own output.  Each limit lies between two
# readings at the published widths on a v5e (my chip runs, PR 32; the table
# is in PERF.md §6): the largest the shipped bfloat16 system gives over its
# seeds, and this file's own arithmetic with weights and activation rounded
# to float8_e4m3, with every mutation a test makes (``mutate``) outside one
# of them.
#   The attention sublayer as a prompt runs it, the expanded path (W_q,
#   W_kva, the kv_a norm, rotary on q_pe and the shared k_pe, W_kvb, the
#   causal walk in chunks of 512, W_o): as shipped 0.0029-0.0046 (twenty
#   seeds); in float8_e4m3 0.19-0.25; no kv_a norm 0.087-0.11, rotary left
#   off k_pe 0.094-0.11, scores over sqrt(128) 0.054-0.061 (the nearest).
LATENT_PROMPT_REL_TOL = 0.015
#   The same sublayer as a tick runs it, the absorbed path: eight slots that
#   hold 5 to 1,023 rows of the one sequence in a paged latent leaf, each
#   slot's next token through W_UK, the pair list, W_UV and W_o, against
#   this file's EXPANDED mathematics at those positions, through the one
#   form of the read the tick runs (pages as [pairs, 576, page]): as
#   shipped 0.0037-0.0060 (ten seeds); in float8_e4m3 0.16-0.25; no kv_a
#   norm 0.11-0.13, rotary left off k_pe 0.16-0.25, scores over sqrt(128)
#   0.077-0.12 (the nearest).
LATENT_DECODE_REL_TOL = 0.015
#   The expert layer alone, this share's routed part plus the shared expert,
#   run as the paged forward runs it: as shipped 0.0034-0.0047; in
#   float8_e4m3 0.30-0.35; routed_scaling_factor 1 0.22-0.24, the shared
#   expert left out 2.5-2.6, top-5 0.23-0.25, the bias added to the gate
#   0.0199-0.0213 (the nearest: beta is small beside sigma): 0.01 is 2.1 x
#   the largest rounding and half the nearest mutation.
EXPERT_LAYER_REL_TOL = 0.01


def spec(cfg, **mutate) -> Dict[str, Any]:
    """What the equations take from the configuration, as plain values; a
    test's mutation overrides one of them."""
    s = {
        "theta": cfg.rope_theta,
        "rope": cfg.rotary_dim,
        "rank": cfg.kv_lora_rank,
        "heads": cfg.num_heads,
        "hd": cfg.dims_per_head,
        "vd": cfg.v_head_dim,
        "score_dim": cfg.dims_per_head,   # scores over sqrt(this)
        "kv_a_norm": True,
        "rope_on_k": True,
        "top_k": cfg.moe_top_k,
        "bias_in_gate": False,
        "routed_scale": cfg.moe_routed_scale,
        "shared": True,
        "held": (cfg.moe_expert_first,
                 cfg.moe_experts_held or cfg.num_experts),
        "eps": cfg.norm_eps,
    }
    s.update(mutate)
    return s


def _check(cfg):
    bad = []
    if not getattr(cfg, "kv_lora_rank", None):
        bad.append("no kv_lora_rank")
    if cfg.norm != "rmsnorm" or cfg.activation != "swiglu":
        bad.append(f"norm={cfg.norm}, activation={cfg.activation}")
    if cfg.position != "rope" or not cfg.rope_interleaved:
        bad.append("rotary other than adjacent pairs")
    if (cfg.moe_score_func != "sigmoid" or not cfg.moe_norm_topk_prob
            or not cfg.moe_select_bias or cfg.dense_layers != 1
            or cfg.layer_pattern is not None):
        bad.append("routing other than sigmoid + bias, renormalised, after "
                   "one dense layer")
    if (cfg.attn_bias or cfg.mlp_bias or cfg.lm_head_bias or cfg.qk_norm
            or cfg.parallel_residual or cfg.post_layernorm
            or cfg.shared_layernorm or cfg.embed_layernorm
            or not cfg.final_norm or not cfg.causal or cfg.tie_embeddings
            or cfg.attn_softmax_scale is not None or cfg.moe_drop_tokens
            or cfg.moe_use_residual or cfg.attention_layers is not None):
        bad.append("an option outside the deepseek_v3 block")
    if bad:
        raise NotImplementedError(
            "reference_kanana2.py covers the Kanana-2 (deepseek_v3) block "
            "only: " + ", ".join(bad))


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, positions, theta: float):
    """x [S, ..., w]: all ``w`` dims rotated in adjacent pairs (2i, 2i + 1)
    by ``position x theta ** (-2i / w)``."""
    w = x.shape[-1]
    inv = theta ** (-jnp.arange(0, w, 2, dtype=F32) / w)
    ang = positions.astype(F32)[:, None] * inv[None, :]          # [S, w/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (w // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


HEADS_AT_A_TIME = 4     # [S, S] scores of all 32 heads do not fit at 3,000


def attention(s, lp, h, positions):
    """Post-norm activations h [S, d] -> the attention sublayer's output
    [S, d]: the expanded mathematics (every head's own keys and values made
    from the latent), causal softmax, W_o."""
    S = h.shape[0]
    H, hd, vd, r, rd = s["heads"], s["hd"], s["vd"], s["rank"], s["rope"]
    nope = hd - rd
    q = (h @ lp["wq"]).reshape(S, H, hd)
    a = h @ lp["wkv_a"]
    c, k_pe = a[:, :r], a[:, r:]
    if s["kv_a_norm"]:
        c = _rmsnorm(c, lp["kv_a_norm_scale"], s["eps"])
    q_pe = rotary(q[..., nope:], positions, s["theta"])
    if s["rope_on_k"]:
        k_pe = rotary(k_pe, positions, s["theta"])
    kv = (c @ lp["wkv_b"]).reshape(S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    ok = positions[:, None] >= positions[None, :]
    out = []
    for g in range(0, H, HEADS_AT_A_TIME):
        hs = slice(g, g + HEADS_AT_A_TIME)
        sc = (jnp.einsum("qhd,khd->hqk", q[:, hs, :nope], k_nope[:, hs])
              + jnp.einsum("qhd,kd->hqk", q_pe[:, hs], k_pe)
              ) / math.sqrt(s["score_dim"])
        p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v[:, hs]))
    return jnp.concatenate(out, axis=1).reshape(S, H * vd) @ lp["wo"]


def expert_weights(s, lp, h2):
    """h2 [S, d] -> the gate of every expert for every token [S, E]: the
    chosen experts' gates (renormalised, scaled), 0 for the rest."""
    score = jax.nn.sigmoid(h2 @ lp["router"])
    E = score.shape[-1]
    choose = score + lp["router_bias"]
    # rank of each expert for each token, ties to the lower index
    lower = jnp.arange(E)[None, :, None] > jnp.arange(E)[None, None, :]
    outranked = ((choose[:, None, :] > choose[:, :, None])
                 | ((choose[:, None, :] == choose[:, :, None]) & lower)
                 ).sum(-1)
    gate = choose if s["bias_in_gate"] else score
    gate = jnp.where(outranked < s["top_k"], gate, 0.0)
    return s["routed_scale"] * gate / gate.sum(-1, keepdims=True)


def _swiglu(h2, w_gate, w_up, w_down):
    g = h2 @ w_gate.astype(F32)
    return (g * jax.nn.sigmoid(g) * (h2 @ w_up.astype(F32))) @ w_down.astype(F32)


def expert_layer(s, lp, h2):
    """h2 [S, d] -> the held experts' part of sum_e g_e expert_e(h2), plus
    the shared experts' MLP."""
    gate = expert_weights(s, lp, h2)
    first, count = s["held"]
    out = jnp.zeros_like(h2)
    for e in range(count):      # lp's stacks hold experts first .. first+count
        out = out + gate[:, first + e:first + e + 1] * _swiglu(
            h2, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    if s["shared"]:
        out = out + _swiglu(h2, lp["shared_w_gate"], lp["shared_w_up"],
                            lp["shared_w_down"])
    return out


def _block(s, dense, lp, x, positions):
    x = x + attention(s, lp, _rmsnorm(x, lp["attn_norm_scale"], s["eps"]),
                      positions)
    h2 = _rmsnorm(x, lp["mlp_norm_scale"], s["eps"])
    return x + (_swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"]) if dense
                else expert_layer(s, lp, h2))


def layers(cfg):
    """``(group, index in the group, dense)`` for each layer in order."""
    return [("full_dense", 0, True)] + [
        ("full_moe", i, False) for i in range(cfg.num_layers - 1)]


def _layer(params, group: str, i: int, round_to=None) -> Dict[str, Any]:
    """One layer's leaves in float32, an expert layer's three routed stacks
    as they are stored (the loop casts one expert at a time).  ``round_to``:
    a dtype every weight is rounded through first (the next precision
    down)."""
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
        for group, i, dense in layers(cfg):
            if group not in blocks:
                blocks[group] = jax.jit(
                    lambda lp, x, dense=dense:
                    _block(s, dense, lp, x, positions))
            if round_to is not None:
                x = x.astype(round_to).astype(F32)
            x = blocks[group](_layer(params, group, i, round_to), x)
        x = _rmsnorm(x, params["final_norm_scale"].astype(F32), cfg.norm_eps)
        head = params["lm_head"]
        if round_to is not None:
            x, head = x.astype(round_to).astype(F32), head.astype(round_to)
        return jnp.dot(x, head.astype(F32))


# rows the eight slots of the decode check hold: a page's edges, a lone
# short slot, the longest the activation allows
DECODE_CHECK_ROWS = (5, 127, 128, 300, 511, 512, 777, 1023)


def layer_checks(cfg, params, seed: int, n_tokens: int = 1024,
                 mutate: Optional[Dict[str, Any]] = None, round_to=None,
                 page_size: int = 128) -> Dict[str, Dict[str, float]]:
    """The system's sublayers ALONE against this file's, on the last expert
    layer and one seeded ``[1, n_tokens, d]`` activation (normal, unit
    variance: what a norm hands on), in the weights' own dtype on the
    system's side: ``{check: {"rel_err", "tol"}}``.

    ``latent_attention_prompt``: the attention sublayer as a prompt's
    prefill runs it, the expanded path (the latent and the shared key row,
    every head's keys and values from them, the causal walk in chunks),
    through W_o.  ``latent_attention_decode``: as a tick runs it, the
    absorbed path: slots that hold ``DECODE_CHECK_ROWS`` rows of the
    sequence in a paged latent leaf (each row written by the system), each
    slot's next token written and read through the pair list; against this
    file's expanded mathematics at those positions.  ``expert_layer``: run
    as the paged forward runs it: the routed leaves the group's whole ``[n*E,
    ...]`` stack with this layer's experts at their offset, the last eighth
    of the tokens masked (their routed part must come back zero; the shared
    expert is every row's and the masked rows are left out of the reading).

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
    group = "full_moe"
    g, n = system.layer_groups(cfg)[group]
    i = n - 1
    leaves = params["layers"][group]
    lp_ref = _layer(params, group, i, round_to)
    h_ref = (h[0].astype(round_to) if round_to is not None
             else h[0]).astype(F32)
    rows = jnp.asarray([r for r in DECODE_CHECK_ROWS if r < n_tokens]
                       or [n_tokens - 1], jnp.int32)

    def layer(leaves):
        return {k: v[i] for k, v in leaves.items()
                if k not in system._EXPERT_LEAVES}

    def system_prompt(leaves, h):
        lp = layer(leaves)
        q, latent = system._qkv_latent(g, lp, h, positions[None])
        k, v = system._latent_expand(g, latent, lp["wkv_b"])
        a = system._attention_causal_block(g, q, k, v, positions[None])
        return system._attn_out(g, lp, a)[0]

    def system_decode(leaves, h):
        lp = layer(leaves)
        q, latent = system._qkv_latent(g, lp, h, positions[None])
        B, ps = rows.shape[0], page_size
        maxp = -(-n_tokens // ps)
        # slot b's own pages hold the first rows[b] rows of the sequence
        paged = jnp.pad(latent[0], ((0, maxp * ps - n_tokens), (0, 0)))
        held = jnp.arange(maxp * ps)[None, :, None] < rows[:, None, None]
        pool = jnp.concatenate([
            jnp.zeros((1, ps, paged.shape[-1]), paged.dtype),
            jnp.where(held, paged[None], 0).reshape(B * maxp, ps, -1)])
        table = 1 + jnp.arange(B * maxp, dtype=jnp.int32).reshape(B, maxp)
        mask = jnp.ones((B, 1), bool)
        attend = system._attend_latent_paged(
            g, {"latent": pool},
            system._paged_write_plan(table, rows, mask, ps),
            system._paged_read_plan(table, rows, mask, ps))
        a, _ = attend(q[0, rows][:, None], latent[0, rows][:, None],
                      lp["wkv_b"])
        return system._attn_out(g, lp, a)[:, 0]

    def system_experts(leaves, h):
        lp = {k: v.reshape(-1, *v.shape[2:])
              if k in system._EXPERT_LEAVES else v[i]
              for k, v in leaves.items()}
        return system._mlp(g, lp, h, jax.random.PRNGKey(0),
                           deterministic=True, token_mask=live[None],
                           expert_offset=jnp.int32(
                               i * (g.moe_experts_held or g.num_experts))
                           )[0][0]

    with jax.default_matmul_precision("highest"):
        want_attn = jax.jit(lambda lp, y: attention(
            s, lp, y, positions))(lp_ref, h_ref)
        want_experts = jax.jit(lambda lp, y: expert_layer(
            s, lp, y))(lp_ref, h_ref)
    n_live = int(n_tokens - n_tokens // 8)
    return {
        "latent_attention_prompt": {
            "rel_err": layer_rel_err(jax.jit(system_prompt)(leaves, h),
                                     want_attn),
            "tol": LATENT_PROMPT_REL_TOL},
        "latent_attention_decode": {
            "rel_err": layer_rel_err(jax.jit(system_decode)(leaves, h),
                                     want_attn[rows]),
            "tol": LATENT_DECODE_REL_TOL},
        "expert_layer": {
            "rel_err": layer_rel_err(
                jax.jit(system_experts)(leaves, h)[:n_live],
                want_experts[:n_live]),
            "tol": EXPERT_LAYER_REL_TOL},
    }
