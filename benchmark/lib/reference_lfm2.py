"""A plain float32 reference of the LFM2-MoE decoder
(``LiquidAI/LFM2-8B-A1B`` ``config.json``, ``model_type`` ``lfm2_moe``; the
equations are Hugging Face ``transformers``' ``modeling_lfm2_moe.py``'s),
independent of ``deepspeed_tpu/models/transformer.py``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
cache, no pages, no batching, no kernels, one sequence, the convolution an
explicit sum over three shifted copies.  ``N`` is an RMSNorm with a learned
scale (eps 1e-5); no bias anywhere::

    x_0    = Embed[id]
    h      = x + op_l(N1(x))                  N1 = operator_norm
    x'     = h + ffn_l(N2(h))                 N2 = ffn_norm
    logits = N_f(x_L) Embed^T                 N_f = embedding_norm; the head
                                              is the embedding transposed

    op_l, layer_types[l] == "conv" (n = N1(x), d = 2,048):
          [B | C | u] = n W_in                (2,048 -> 3 x 2,048)
          z   = B . u
          c_t = sum_{k=0..2} w[k] . z_{t-2+k} (depthwise, causal, zeros before
                                              position 0, no bias, NO
                                              activation)
          op  = (C . c) W_out
          a sequence's state after position t is (z_{t-1}, z_t)
    op_l, "full_attention": q, k, v = n W_q, n W_k, n W_v; 32 / 8 / 8 heads
          of 64; q_h = RMSNorm_64(q_h; w_q), k_h = RMSNorm_64(k_h; w_k) (over
          each head's 64 dims AFTER the split, one scale of 64 for all
          heads); rotary over the whole head width, half-split, theta 1e6;
          causal softmax(q k^T / sqrt(64)) v, query head h reading KV head
          h // 4; W_o
    ffn_l, l < 2:  W_2 (silu(W_1 m) . W_3 m), 7,168 wide
    ffn_l, l >= 2: s = sigmoid(m W_g) over 32 experts; the 4 chosen are the
          top 4 of s + expert_bias (ties to the lower index); their gates are
          s there (WITHOUT the bias) / (their sum + 1e-6) x
          routed_scaling_factor (1); sum_e gate_e SwiGLU_e(m), 1,792 wide; no
          shared expert, no token dropped

It reads the parameter tree by the names ``init_params`` gives the leaves:
``layers/conv_dense`` (layers 0, 1), ``layers/full_moe`` and
``layers/conv_moe``, each stacked in the order its layers appear in
``layer_pattern``; ``conv_in`` is ``[B | C | u]`` side by side, ``conv_w``
the taps ``[3, d]`` (tap 2 meets the position itself), ``router_bias`` the
``expert_bias``.  The names are the interface, the arithmetic is its own.  One
layer's weights are upcast at a time, an expert layer's one expert at a time
and the head in row blocks of the embedding, so the float32 copies fit beside
the system's bfloat16 weights on one chip.

What each side computes in (``assumed`` in the configuration's file): the
system holds weights and activations in bfloat16, the convolution's tail in
bfloat16 (it is ``z`` as the layer made it), the router's scores, the choice
and the gates in float32, the three-term sum in float32; this file holds
everything in float32.

Departures from the checkpoint, each also under ``assumed``: (1) head width
64 = 2,048 / 32 (the config gives none); (2) the head tied to the embedding
(no key; the published 8.3 B counts it so); (3) QK-norm by head, the order of
the norms, the gate-before / gate-after form of the operator and the
router's 1e-6 are the family's published modeling code's, the config carries
no key for them; (4) ``expert_bias`` is zeros in a checkpoint's
initialisation and is drawn here small and non-zero (quantiles of a normal,
a fifth of the scores' spread), so that the selection and the gates can be
told apart; the taps are drawn U(-1/2, 1/2), every other weight normal with
std 0.02.  Weights are random from ``--seed``, never the checkpoint's.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_BLOCK = 16384      # rows of the embedding upcast at a time for the head

# The logits of the whole cut, at the published widths on a v5e (my chip
# runs, PR 54: twenty-three readings on twenty seeds of the parity's own
# draw, 700 prompt tokens and 16 decode steps; PERF.md section 6).  **Routing
# flips, not arithmetic, set this reading**: top 4 of 32 sigmoid scores lie
# 0.02-0.03 apart at the fourth place and the system's bfloat16 stream moves
# a score by ~0.004, so at twelve expert layers nearly every token has had
# its fourth expert swapped somewhere (each swap a gate of ~0.25 between two
# experts): the MEDIAN token of a prompt reads 0.14-0.19 of the logits' root
# mean square (0.905), where a layer alone, both sides routing one
# activation, reads 0.002-0.006 of max|ref| (``layer_checks``).  A block is
# therefore read by its largest token after the worst one in ``FLIP_SHARE``
# (``reference_mimo_v2``'s rule: a fault on one token in 128, a page's edge,
# still shows), a single token (a decode step) as it is, both against
# ``PEAK_OVER_RMS`` x ``FLIP_ROOM`` x the reference's rms
# (``reference_kanana2``'s form): with ``FLIP_ROOM`` 5 the kind's 0.05 is
# 1.24 in the logits' own unit.  As shipped a prompt reads 0.68-0.80 of that
# unit (0.027-0.032 through this function) and the worst of 16 decode steps
# 0.37-0.83 (0.015-0.033); the worst single token of 2,100 prompt tokens
# read 0.83.  This file's own forward in float8_e4m3, the nearest precision
# below, reads 1.73-1.77 on a prompt (0.070-0.071) and 1.64-1.66 on the
# worst of 16 steps (0.066-0.067): not correct by either.  ``FLIP_ROOM`` was
# chosen after those readings (the chip runs were made at 2 and at 4.5; a
# reading goes as its inverse): 1.5 x above the largest shipped reading, a
# single token's, whose tail is the heavier, and 1.3-1.4 x under the float8
# reference's; what this limit cannot see (a term that moves the logits by
# less than the flips do) the per-layer limits below hold.
FLIP_SHARE = 200
FLIP_ROOM = 5.0
PEAK_OVER_RMS = 5.5


def rel_err(got, want) -> float:
    """The largest |got - want| of a token's logits over ``PEAK_OVER_RMS`` x
    ``FLIP_ROOM`` x the reference's root mean square (``reference_kanana2``'s
    reading and its reason): of one token as it is, of a block ``[S, V]`` the
    largest after the worst ``S // FLIP_SHARE`` tokens.  Any other shape:
    max|diff| / max|want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
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
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / np.abs(want).max())


# Single pieces of the system against this file's at the published widths on
# a v5e (my chip runs, PR 54: twenty seeds as shipped, three in
# float8_e4m3, one each mutation; PERF.md section 6), max|diff| / max|ref|
# on the piece's own output.  Each limit lies between the largest the shipped system gives
# over its seeds (bfloat16 weights and activations against this file's
# float32) and what this file gives with weights and activations rounded
# through float8_e4m3, the nearest precision below, with room on both sides
# and every mutation a test makes (``mutate``) outside one of them.
#   The operator of the last conv layer over a padded prompt (700 real
#   tokens in a block of 1,024), a seeded activation of unit variance: as
#   shipped 0.0042-0.0052; in float8_e4m3 0.143-0.155; with a SiLU behind
#   the convolution 1.01.
CONV_OPERATOR_REL_TOL = 0.02
#   The slot's tail after the paged prefill of 700 tokens in a 1,024 bucket
#   into slot 1 of 3, and again after 16 teacher-forced ticks, the FIRST conv
#   layer's row against this file's (z_{t-1}, z_t): as shipped 0.0030-0.0071
#   (z is the product of two bfloat16 projections, rounded once more); in
#   float8_e4m3 0.047-0.067; z alone through 4 exponent and 3 mantissa
#   bits (``tail_dtype``) 0.034-0.038.
TAIL_REL_TOL = 0.018
#   The attention sublayer of the last attention layer (QK-norm by head,
#   rotary, grouped heads, W_o) over the same block: as shipped
#   0.0019-0.0045; in float8_e4m3 0.083-0.093; the QK-norm over the whole
#   projections 0.059 (the nearest), none 0.105.
ATTN_REL_TOL = 0.015
#   The dense MLP of layer 1: as shipped 0.0032-0.0052; in float8_e4m3
#   0.084-0.090.
DENSE_MLP_REL_TOL = 0.015
#   The last expert layer as the paged forward runs it (the group's whole
#   ``[n * E, ...]`` stacks, this layer's experts at their offset, the last
#   eighth of the tokens masked), over the tokens whose choice is not a near
#   tie (``CHOICE_MARGIN``): as shipped 0.0040-0.0056; in float8_e4m3
#   0.67-0.76; the bias added to the gates 0.061 (the nearest: the bias is
#   small beside the scores), top 3 for 4 0.445.
EXPERT_LAYER_REL_TOL = 0.012
# Both sides route the SAME bfloat16 activation in float32, so their scores
# differ by float32's rounding alone (1e-6); a token whose 4th and 5th
# biased scores lie closer than this may still go either way, and is left
# out of ``expert_layer`` (a flip there is no fault).  Their share is held
# under ``NEAR_TIE_SHARE`` (0 to 5 tokens of 896 on the chip, 0-0.56%):
# more of them would mean that the scores themselves are off.
CHOICE_MARGIN = 1e-4
NEAR_TIE_SHARE = 0.02
CHECK_PROMPT, CHECK_BLOCK, CHECK_DECODE = 700, 1024, 16
TOY_CHECK = (45, 64, 8)
# The limits are measured where they judge, at the published widths.  At the
# CPU rehearsal's toy widths the same bfloat16 roundings are spread over a
# few dozen elements instead of thousands and a reading swings with the
# seed: a model under 1,024 hidden channels is read against three times each
# limit.
TOY_HIDDEN, TOY_ROOM = 1024, 3.0

_GROUP = {("conv", True): "conv_dense", ("conv", False): "conv_moe",
          ("full", True): "full_dense", ("full", False): "full_moe"}


def plan(cfg) -> List[Tuple[str, int]]:
    """``(group, index in the group)`` of each layer run, in order: the
    first ``num_layers`` entries of the published pattern, the first
    ``dense_layers`` of them with a dense MLP."""
    seen: Dict[str, int] = {}
    out = []
    for i, kind in enumerate(cfg.layer_pattern[:cfg.num_layers]):
        group = _GROUP[kind, i < cfg.dense_layers]
        out.append((group, seen.get(group, 0)))
        seen[group] = seen.get(group, 0) + 1
    return out


def spec(cfg, **mutate) -> Dict[str, Any]:
    """What the equations take from the configuration, as plain values; a
    test's mutation overrides one of them (``qk_norm`` "whole" or None,
    ``conv_act`` True, ``bias_in_gate`` True, ``top_k``, ``gate_eps``,
    ``tail_dtype``)."""
    s = {
        "eps": cfg.norm_eps, "heads": cfg.num_heads, "kv_heads": cfg.kv_heads,
        "hd": cfg.dims_per_head, "theta": cfg.rope_theta,
        "taps": cfg.conv_taps, "top_k": cfg.moe_top_k,
        "routed_scale": cfg.moe_routed_scale,
        "gate_eps": cfg.moe_norm_topk_eps,
        "qk_norm": "head", "conv_act": False, "bias_in_gate": False,
        # the dtype z is rounded through before the convolution reads it
        # (the tail's, and the block's own rows alike)
        "tail_dtype": F32,
    }
    s.update(mutate)
    return s


def _check(cfg):
    bad = []
    pattern = tuple(cfg.layer_pattern or ())[:cfg.num_layers]
    if not getattr(cfg, "conv_taps", 0) or not pattern or any(
            k not in ("conv", "full") for k in pattern):
        bad.append("no layer_pattern of conv and full layers")
    if cfg.norm != "rmsnorm" or cfg.activation != "swiglu":
        bad.append(f"norm={cfg.norm}, activation={cfg.activation}")
    if cfg.position != "rope" or cfg.rotary_dim or cfg.rope_interleaved:
        bad.append("rotary that is not half-split over the whole head")
    if (cfg.qk_norm != "head" or cfg.conv_bias or cfg.attn_bias
            or cfg.mlp_bias or cfg.parallel_residual or cfg.post_layernorm
            or cfg.shared_layernorm or cfg.embed_layernorm
            or not cfg.final_norm or not cfg.causal or not cfg.tie_embeddings
            or cfg.attn_softmax_scale is not None or cfg.kv_lora_rank
            or cfg.attention_layers is not None or cfg.ssm_heads
            or getattr(cfg, "linear_heads", 0) or cfg.norm_after
            or cfg.sandwich_norm or cfg.loop_passes != 1
            or cfg.v_head_dim not in (None, cfg.dims_per_head)
            or cfg.moe_score_func != "sigmoid" or not cfg.moe_select_bias
            or not cfg.moe_norm_topk_prob or cfg.moe_shared_experts
            or cfg.moe_experts_held or cfg.moe_drop_tokens
            or (cfg.embed_multiplier, cfg.lm_head_multiplier,
                cfg.residual_multiplier) != (1.0, 1.0, 1.0)):
        bad.append("an option outside the lfm2_moe block")
    if bad:
        raise NotImplementedError(
            "reference_lfm2.py covers the LFM2-MoE block only: "
            + ", ".join(bad))


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _through(x, dtype):
    """float32 ``x`` rounded through ``dtype``'s exponent and mantissa.  Not
    ``x.astype(dtype).astype(F32)``: inside a jitted block the TPU's compiler
    may keep the excess precision and drop the pair."""
    if dtype == F32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def rotary(x, positions, theta: float):
    """x [S, H, hd] rotated over its whole width, half-split: dim i pairs
    with dim i + hd / 2 at frequency theta^(-2i / hd)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def attention(s, lp, n, positions):
    """n [S, d] (normed) -> the attention operator's output [S, d]."""
    S = n.shape[0]
    H, G, hd = s["heads"], s["kv_heads"], s["hd"]
    q, k, v = n @ lp["wq"], n @ lp["wk"], n @ lp["wv"]
    if s["qk_norm"] == "whole":     # a test's: OLMoE's, before the split
        q = _rmsnorm(q, jnp.tile(lp["q_norm_scale"], H), s["eps"])
        k = _rmsnorm(k, jnp.tile(lp["k_norm_scale"], G), s["eps"])
    q, k, v = (a.reshape(S, -1, hd) for a in (q, k, v))
    if s["qk_norm"] == "head":
        q = _rmsnorm(q, lp["q_norm_scale"], s["eps"])
        k = _rmsnorm(k, lp["k_norm_scale"], s["eps"])
    q, k = rotary(q, positions, s["theta"]), rotary(k, positions, s["theta"])
    k, v = (jnp.repeat(a, H // G, axis=1) for a in (k, v))
    sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    ok = positions[:, None] >= positions[None, :]
    p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd) @ lp["wo"]


def conv_operator(s, lp, n):
    """n [S, d] (normed) -> ``(the conv operator's output [S, d], z [S, d])``:
    the gate before, three shifted copies, the gate after."""
    S, d = n.shape
    K = s["taps"]
    p = n @ lp["conv_in"]
    B, C, u = p[:, :d], p[:, d:2 * d], p[:, 2 * d:]
    z = _through(B * u, s["tail_dtype"])
    ext = jnp.concatenate([jnp.zeros((K - 1, d), F32), z])
    c = sum(ext[k:k + S] * lp["conv_w"][k] for k in range(K))
    if s["conv_act"]:
        c = _silu(c)
    return (C * c) @ lp["conv_out"], z


def _swiglu(m, w_gate, w_up, w_down):
    g = m @ w_gate.astype(F32)
    return (_silu(g) * (m @ w_up.astype(F32))) @ w_down.astype(F32)


def expert_scores(s, lp, m):
    """m [S, d] -> ``(every expert's gate for every token [S, E], 0 for those
    not chosen; the margin [S] between the last chosen and the first
    unchosen biased score)``."""
    score = jax.nn.sigmoid(m @ lp["router"])
    E = score.shape[-1]
    choose = score + lp["router_bias"]
    # rank of each expert for each token, ties to the lower index
    lower = jnp.arange(E)[None, :, None] > jnp.arange(E)[None, None, :]
    outranked = ((choose[:, None, :] > choose[:, :, None])
                 | ((choose[:, None, :] == choose[:, :, None]) & lower)
                 ).sum(-1)
    chosen = outranked < s["top_k"]
    gate = jnp.where(chosen, choose if s["bias_in_gate"] else score, 0.0)
    gate = s["routed_scale"] * gate / (gate.sum(-1, keepdims=True)
                                       + s["gate_eps"])
    margin = (jnp.where(chosen, choose, jnp.inf).min(-1)
              - jnp.where(chosen, -jnp.inf, choose).max(-1))
    return gate, margin


def expert_layer(s, lp, m):
    """m [S, d] -> sum_e gate_e SwiGLU_e(m), one expert at a time."""
    gate, _ = expert_scores(s, lp, m)
    out = jnp.zeros_like(m)
    for e in range(gate.shape[-1]):
        out = out + gate[:, e:e + 1] * _swiglu(
            m, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    return out


def block(s, lp, x, positions):
    """One layer: ``(its output [S, d], z [S, d] of a conv layer or None)``.
    Which operator and which ffn show in its leaves."""
    n = _rmsnorm(x, lp["attn_norm_scale"], s["eps"])
    if "conv_in" in lp:
        op, z = conv_operator(s, lp, n)
    else:
        op, z = attention(s, lp, n, positions), None
    h = x + op
    m = _rmsnorm(h, lp["mlp_norm_scale"], s["eps"])
    ffn = (expert_layer(s, lp, m) if "router" in lp
           else _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"]))
    return h + ffn, z


def _layer(params, group: str, i: int, round_to=None,
           routed: bool = True) -> Dict[str, Any]:
    """Layer ``i`` of ``group`` in float32, an expert layer's three routed
    stacks as they are stored (the loop upcasts one expert at a time; left
    out under ``routed=False``: an operator reads none of them);
    ``round_to``: a dtype every weight is rounded through first (the next
    precision down)."""
    def f32(a):
        return (a.astype(round_to) if round_to is not None else a).astype(F32)

    leaves = params["layers"][group]
    stacks = ("w_gate", "w_up", "w_down") if "router" in leaves else ()
    return {k: (v[i] if round_to is None else v[i].astype(round_to))
            if k in stacks else f32(v[i]) for k, v in leaves.items()
            if routed or k not in stacks}


def _logits(s, params, x, round_to=None):
    """The final norm and the tied head over ``x [S, d]``, ``HEAD_BLOCK``
    rows of the embedding upcast at a time, each block of logits to the host
    as it is made."""
    x = _rmsnorm(x, params["final_norm_scale"].astype(F32), s["eps"])
    if round_to is not None:
        x = x.astype(round_to).astype(F32)
    embed = params["embed"]
    out = []
    for r in range(0, embed.shape[0], HEAD_BLOCK):
        w = embed[r:r + HEAD_BLOCK]
        if round_to is not None:
            w = w.astype(round_to)
        out.append(np.asarray(jnp.dot(x, w.astype(F32).T)))
    return np.concatenate(out, axis=-1)


def forward(cfg, params, tokens, round_to=None, rows=None, **mutate):
    """tokens [S] int -> ``(logits [S, V] float32 on the host (of the
    positions ``rows`` alone where given), z [S, d] of every conv layer in
    order)``.  A layer at a time from the leaves as they are stored.
    ``round_to``: a dtype every weight and every layer's input is rounded
    through."""
    _check(cfg)
    s = spec(cfg, **mutate)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        blocks: Dict[str, Any] = {}
        zs = []
        for group, i in plan(cfg):
            if group not in blocks:
                blocks[group] = jax.jit(
                    lambda lp, x: block(s, lp, x, positions))
            if round_to is not None:
                x = x.astype(round_to).astype(F32)
            x, z = blocks[group](_layer(params, group, i, round_to), x)
            if z is not None:
                zs.append(z)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return _logits(s, params, x, round_to), zs


def reference_logits(cfg, params, tokens, round_to=None, **mutate):
    """tokens [S] int -> logits [S, V] float32."""
    return forward(cfg, params, tokens, round_to=round_to, **mutate)[0]


def layer_checks(cfg, params, seed: int, n_prompt: Optional[int] = None,
                 block_tokens: Optional[int] = None,
                 n_decode: Optional[int] = None, page_size: int = 128,
                 mutate: Optional[Dict[str, Any]] = None,
                 round_to=None) -> Dict[str, Dict[str, float]]:
    """Pieces of the system ALONE against this file's, in the weights' own
    dtype on the system's side: ``{check: {"rel_err", "tol"}}``.

    ``conv_operator`` / ``attention_operator``: the last conv layer's and
    the last attention layer's operator over a seeded ``[1, block_tokens,
    d]`` activation of unit variance (what a norm hands on) of which
    ``n_prompt`` positions are real.  ``dense_mlp``: layer 1's MLP over it.
    ``expert_layer``: the last expert layer as the paged forward runs it
    (the group's whole stacks, this layer's experts at their offset, the
    last eighth of the tokens masked: their part must come back zero), over
    the tokens whose choice is no near tie; ``expert_near_ties`` their share.
    ``tail_after_prefill`` / ``tail_after_decode``: ``n_prompt`` seeded
    tokens padded to ``block_tokens`` through the system's paged prefill into
    slot 1 of 3, then ``n_decode`` teacher-forced ticks; the first conv
    layer's tail row against this file's ``(z_{t-1}, z_t)``.
    ``other_slots_untouched``: the rows of slots 0 and 2 stay zero.

    ``mutate`` (a test's) changes this file's side (:func:`spec`);
    ``round_to`` rounds this file's weights and activations through a
    narrower dtype.  Either must push a check past its limit.  The three
    lengths default to ``CHECK_*`` (``TOY_CHECK`` under ``TOY_HIDDEN`` hidden
    channels)."""
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models import transformer as system

    _check(cfg)
    s = spec(cfg, **(mutate or {}))
    toy = cfg.hidden_size < TOY_HIDDEN
    room = TOY_ROOM if toy else 1.0
    sizes = TOY_CHECK if toy else (CHECK_PROMPT, CHECK_BLOCK, CHECK_DECODE)
    n_prompt, block_tokens, n_decode = (
        b if a is None else a
        for a, b in zip((n_prompt, block_tokens, n_decode), sizes))
    dtype = params["embed"].dtype
    rng = np.random.default_rng(seed)
    layers = plan(cfg)
    groups = system.layer_groups(cfg)
    out: Dict[str, Dict[str, float]] = {}

    h = jnp.asarray(rng.standard_normal(
        (1, block_tokens, cfg.hidden_size)).astype(np.float32)).astype(dtype)
    positions = jnp.arange(block_tokens, dtype=jnp.int32)
    real = (positions < n_prompt)[None]
    h_ref = (h[0, :n_prompt].astype(round_to) if round_to is not None
             else h[0, :n_prompt]).astype(F32)

    def last(*names):
        group = next(g for g in reversed([g for g, _ in layers])
                     if g in names)
        return group, max(j for g, j in layers if g == group)

    def small(group, i):
        """Layer ``i`` of the group's leaves but its routed stacks."""
        leaves = params["layers"][group]
        return {k: v[i] for k, v in leaves.items()
                if not ("router" in leaves and k in system._EXPERT_LEAVES)}

    def plain(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    def held(name, got, want, tol):
        out[name] = {"rel_err": layer_rel_err(got, want), "tol": room * tol}

    # -- the conv operator over a padded prompt
    group, i = last("conv_moe", "conv_dense")
    g = groups[group][0]
    got = jax.jit(lambda lp, h: system._conv_mixer(g, lp, h, real)[0][0])(
        small(group, i), h)[:n_prompt]
    held("conv_operator", got, plain(
        lambda lp, n: conv_operator(s, lp, n)[0],
        _layer(params, group, i, round_to, routed=False), h_ref),
        CONV_OPERATOR_REL_TOL)

    # -- the attention operator
    group, i = last("full_moe", "full_dense")
    g = groups[group][0]

    def system_attention(lp, h):
        q, k, v = system._qkv(g, lp, h, positions[None])
        a, _ = system._attend_full(g, positions[None])(q, k, v)
        return system._attn_out(g, lp, a)[0]

    got = jax.jit(system_attention)(small(group, i), h)[:n_prompt]
    held("attention_operator", got, plain(
        lambda lp, n: attention(s, lp, n, positions[:n_prompt]),
        _layer(params, group, i, round_to, routed=False), h_ref),
        ATTN_REL_TOL)

    # -- the dense MLP
    group = "conv_dense" if "conv_dense" in groups else "full_dense"
    g, n = groups[group]
    got = jax.jit(lambda lp, h: system._dense_mlp(g, lp, h)[0])(
        small(group, n - 1), h)[:n_prompt]
    held("dense_mlp", got, plain(
        lambda lp, m: _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"]),
        _layer(params, group, n - 1, round_to), h_ref), DENSE_MLP_REL_TOL)

    # -- the expert layer, as the paged forward runs it
    group, i = last("conv_moe", "full_moe")
    g = groups[group][0]
    live = positions < block_tokens - block_tokens // 8

    def system_experts(leaves, h):
        lp = {k: v.reshape(-1, *v.shape[2:])
              if k in system._EXPERT_LEAVES else v[i]
              for k, v in leaves.items()}
        return system._mlp(g, lp, h, jax.random.PRNGKey(0),
                           deterministic=True, token_mask=live[None],
                           expert_offset=jnp.int32(i * g.num_experts))[0][0]

    got = np.asarray(jax.jit(system_experts)(params["layers"][group], h),
                     np.float32)
    lp = _layer(params, group, i, round_to)
    h_all = (h[0].astype(round_to) if round_to is not None
             else h[0]).astype(F32)
    want = np.asarray(plain(lambda lp, m: expert_layer(s, lp, m), lp, h_all))
    margin = np.asarray(plain(lambda lp, m: expert_scores(s, lp, m)[1],
                              lp, h_all))
    n_live = int(live.sum())
    sure = margin[:n_live] >= CHOICE_MARGIN
    held("expert_layer", got[:n_live][sure], want[:n_live][sure],
         EXPERT_LAYER_REL_TOL)
    out["expert_near_ties"] = {"rel_err": float(1.0 - sure.mean()),
                               "tol": room * NEAR_TIE_SHARE}
    out["expert_masked_rows_zero"] = {
        "rel_err": float(np.abs(got[n_live:]).max()), "tol": 0.0}

    # -- the slot's tail through the paged prefill and the ticks
    model = CausalLM(cfg)
    total = n_prompt + n_decode
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, total))
                       .astype(np.int32))
    n_pages = -(-max(total, block_tokens) // page_size)
    cache = model.init_paged_cache(1 + n_pages, page_size, dtype=dtype,
                                   slots=3)
    table = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)[None]
    slot = jnp.ones((1,), jnp.int32)
    step = jax.jit(lambda p, t, c, start, mask: model.apply_paged(
        p, t, c, table, start, mask, state_slot=slot,
        logits_at=jnp.maximum(mask.sum(1) - 1, 0)))
    prompt = jnp.zeros((1, block_tokens), jnp.int32).at[:, :n_prompt].set(
        toks[:, :n_prompt])
    _, cache = step(params, prompt, cache, jnp.zeros((1,), jnp.int32), real)
    K, d = cfg.conv_taps, cfg.hidden_size

    def first_tail(cache):
        return np.asarray(cache["conv_tail"][0, 1], np.float32).reshape(
            K - 1, d)

    after_prefill = first_tail(cache)
    for j in range(n_decode):
        _, cache = step(params, toks[:, n_prompt + j:n_prompt + j + 1],
                        cache, jnp.full((1,), n_prompt + j, jnp.int32),
                        jnp.ones((1, 1), bool))
    after_decode = first_tail(cache)
    _, zs = forward(cfg, params, toks[0], round_to=round_to,
                    rows=(total - 1,), **(mutate or {}))
    z = np.asarray(zs[0])
    held("tail_after_prefill", after_prefill, z[n_prompt - K + 1:n_prompt],
         TAIL_REL_TOL)
    held("tail_after_decode", after_decode, z[total - K + 1:total],
         TAIL_REL_TOL)
    out["other_slots_untouched"] = {
        "rel_err": float(np.abs(np.asarray(
            cache["conv_tail"][:, (0, 2)], np.float32)).max()), "tol": 0.0}
    return out
