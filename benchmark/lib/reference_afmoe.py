"""A plain float32 reference of the Trinity decoder (``arcee-ai/
Trinity-Large-Preview`` ``config.json``, ``model_type`` ``afmoe``),
independent of ``deepspeed_tpu/models/transformer.py`` and
``deepspeed_tpu/moe/``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
kernels, no cache, no pages, no ring, no sort, no grouped matmul, one
sequence.  ``x0 = E[token] * sqrt(d)`` (``mup_enabled``: the embedding's
multiplier and nothing else).  Layer ``l`` of kind ``k(l)`` (``layer_types``:
sliding or full), RMSNorm (eps 1e-5), no biases::

    a  = N1(x)
    q  = a Wq -> 48 heads x 128     k = a Wk,  v = a Wv -> 8 x 128
    g  = a Wg -> 48 x 128           (the gate, from the same normed input)
    q, k RMS-normed over each head's 128 dims, one learned scale each
    sliding:  q and k rotated over all 128 dims, pairs (i, i + 64), theta 1e4
    full:     NO position at all
    s_ij = q_i k_j / sqrt(128),  j <= i,  and on a sliding layer i - j <= 4,095
    o  = softmax(s) v
    y  = (o * sigmoid(g)) Wo
    x += N2(y)
    m  = N3(x)
    l < num_dense_layers:  f = Wd (silu(Wg' m) * (Wu m))        (12,288 wide)
    others:  z = m Wr (float32, 256 wide)      s = sigmoid(z)
             the 4 experts with the largest s_e + b_e (ties to the lower
             index; b = expert_bias enters the choice and not the gate; one
             group, no group limit)
             gate_e = s_e / (sum_chosen s + 1e-20) * 2.448
             f = shared(m) + sum over the chosen e THAT ARE HELD of
                 gate_e W_down,e (silu(m W_gate,e) * (m W_up,e))
    x += N4(f)

``held = (first, count)`` is one chip's share of the experts: the choice and
the gates are over all 256, what an absent expert would add is left out, and
the shared expert, which every chip of a stage computes alike, is whole (all
the experts are held when the configuration has no share).  Final RMSNorm,
untied head.

Departures from the published description, each also in the configuration
file's ``assumed``: the four norms' placement is the modeling file's
(``input_layernorm``, ``post_attention_layernorm`` on attention's OUTPUT,
``pre_mlp_layernorm``, ``post_mlp_layernorm`` on the MLP's output);
"depth-scaled" is read as an initialisation of N2's and N4's scales, not as
arithmetic; ``load_balance_coeff`` is training's and appears nowhere; the
weights are random from a seed, not the checkpoint.

It reads the parameter tree by the names ``init_params`` gives the leaves
(``layers/<kind>_<dense|moe>/wq`` stacked over the group's layers, in the
order the layers have in ``layer_pattern``; ``wg`` the gate's projection,
``attn_post_norm_scale`` / ``mlp_post_norm_scale`` N2 / N4, ``shared_w_*``
the shared expert): the names are the interface, the arithmetic is its own.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# How the logits are read.  Top-4-of-256 routing is a discrete choice: where
# the 4th and 5th of ``s + b`` lie closer than bfloat16 activations resolve
# them, the system and a float32 reference choose different experts.  Under
# a held share that is all or nothing for the token: with one expert in
# sixteen here, a flip that takes a held expert in or out adds or removes a
# whole expert's output, x 2.448, in front of N4.  At the published widths
# on a v5e (my chip runs, PR 58: two seeds, 4,500 prompt tokens and 64
# decode tokens each, every token's max|diff| over 5.5 x the reference's
# root mean square) the readings are TWO populations and nothing between
# them: **0.0062-0.0093 on a token no such flip reached** (median 0.0071,
# 95th percentile 0.0083-0.0086, the same at every position of the prompt),
# **0.068-0.171 on one it did: 2.7% and 3.8% of a prompt's tokens, 2 and 4
# of 64 decode tokens**.  The reference against ITSELF with every layer's
# input rounded to bfloat16 shows the same tail (1.5-1.8% of the tokens over
# 0.05): the flips are bfloat16's, not a fault of the program's.  MiMo's
# flips read 0.03-0.05 (every expert's part is a sixteenth of a sum of
# eight, no norm behind it) and Kanana's 0.02-0.05; neither file's rule
# clears these.
#
# A block ``[S, V]`` is read by its largest token after the worst ``S //
# FLIP_SHARE`` = one in twelve, twice the larger share of flipped tokens
# seen: what is left is the population rounding alone makes, read on the
# plain scale against the kind's 0.05.  What a fault on fewer than one token
# in twelve would do to a prompt (a page's edge) is held by ``layer_checks``,
# where both sides route one activation and nothing flips: the prompt's walk
# AND the tick's read through the ring and the pages, every token read.
FLIP_SHARE = 12
# A single token (a decode step) cannot leave itself out: it is read against
# ``FLIP_ROOM`` x the scale, so that the kind's 0.05 is 0.25 of 5.5 x rms,
# over the worst flipped token of some 9,400 read (0.172, a decode step of
# the fourteenth run; the worst of a prompt's 0.171).  A token no flip
# reached reads 0.0012-0.0019 so, a flipped one 0.014-0.034; a tick that
# reads another slot's page or a ring's page a turn too old reads over 0.1
# (0.535 before the factor for one misplaced page, PERF.md PR 21).
FLIP_ROOM = 5.0
# max|logit| of a block in units of its root mean square (5.5 over a few
# thousand tokens x 25,024 Gaussian logits, 4.1 over one token's): every
# reading is taken against 5.5 x rms, so that a decode step and a prompt are
# read on one scale.
PEAK_OVER_RMS = 5.5


def rel_err(got, want) -> float:
    """The largest |got - want| of a token's logits, over ``PEAK_OVER_RMS``
    x the reference's root mean square: of a block ``[S, V]`` the largest
    after the worst ``S // FLIP_SHARE`` tokens; of one token (``[V]``) as it
    is, over ``FLIP_ROOM`` x that scale (see both).  Any other shape:
    max|diff| / max|want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if want.ndim > 2 or got.shape != want.shape:
        return float(np.abs(got - want).max() / np.abs(want).max())
    scale = PEAK_OVER_RMS * float(np.sqrt(np.mean(want * want)))
    if want.ndim == 1:
        return float(np.abs(got - want).max() / (FLIP_ROOM * scale))
    per_token = np.sort(np.abs(got - want).max(-1))
    return float(per_token[len(per_token) - 1 - len(per_token) // FLIP_SHARE]
                 / scale)


def layer_rel_err(got, want) -> float:
    """max|diff| / max|ref| on a sublayer's own output."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


# Single layers of the system against this file's, each group's last layer,
# a seeded [1, 5,120, d] activation of unit variance, max|diff| / max|ref| on
# the sublayer's own output (through N2 / N4, whose scale multiplies both
# sides alike).  Needed beside the logits check: both sides route ONE
# activation here, so nothing flips and every token is read.  Each limit lies
# between two readings at the published widths on a v5e (my chip runs, PR
# 58; PERF.md section 6): the largest the shipped bfloat16 system gives over
# sixteen seeds, and this file's own arithmetic with weights and activation
# rounded to float8_e4m3, with every mutation a test makes (``mutate``)
# outside it.
#   a sliding layer's attention as a prompt walks it (q, k, v, g, the
#   QK-norm, rotary, the window in the walk's chunks of 512, the gate, Wo,
#   N2): as shipped 0.0072-0.0089; in float8_e4m3 0.119; the window less a
#   page 0.165 (the nearest), halved 0.75, no gate 0.43, no QK-norm 0.45, no
#   rotary 1.01.  The same sublayer as a tick reads it through the ring
#   (``window_tick``: eight slots under, at and past the window):
#   0.0063-0.0096.
WINDOW_ATTN_REL_TOL = 0.025
#   a full layer's attention (no position): as shipped 0.0060-0.0077; in
#   float8_e4m3 0.088; no gate 0.44, no QK-norm 0.43, rotary added 0.96.
#   Through the pages (``full_tick``): 0.0051-0.0078.  0.025 is 2.6 x the
#   largest rounding of the four checks and under 0.29 of the nearest
#   float8 reading.
FULL_ATTN_REL_TOL = 0.025
#   THE SOFTMAX'S PRECISION IS NOT HELD BY ANY LIMIT, and cannot be: with
#   this file's scores and probabilities rounded to bfloat16
#   (``softmax_dtype``) the four checks read 0.0067-0.0089 against
#   0.0059-0.0086 on the same two seeds.  The system keeps the scores, the
#   running maximum and the sum in float32 and hands bfloat16 probabilities
#   to the product with V; rounding the scores too moves the output by a
#   fourteenth of what the bfloat16 weights and activations already do.
#   the expert layer alone, this share's routed part + the shared expert,
#   through N4 (the larger of the window and the full group's): as shipped
#   0.0056-0.0077; in float8_e4m3 0.585; **the router's product in bfloat16
#   (``router_dtype``; the stated precision is float32) 0.416-0.423**: some
#   of 4,480 tokens then choose another expert, and one that gains or loses
#   a held expert moves by its whole output; the bias added to the gate
#   0.034 (the nearest), scale 1 0.36, top-3 0.53, no renormalisation 0.59,
#   softmax for sigmoid 0.78, the held range shifted by one expert 0.82, no
#   shared expert 1.08.  0.02 is 2.6 x the largest rounding and under 0.59
#   of the nearest mutation.
EXPERT_LAYER_REL_TOL = 0.02
# The logits (``rel_err`` above, against the serve-backlog kind's 0.05), a
# 4,500-token prompt (past the window: the ring wraps inside it) and 16
# decode steps through both pools, fourteen runs of the cell, each a seed of
# its own: the prompt 0.0080-0.0085 (the token at the 92nd percentile), the
# worst of 16 decode steps 0.0015-0.0018 where no flip reached one (nine
# runs) and 0.0125-0.0345 where one did (five).  This file's own forward in
# float8_e4m3: the prompt 0.151 (its BEST token reads 0.090: not correct, by
# the kind's limit on the prompt), the worst of 64 decode steps 0.033
# through ``FLIP_ROOM`` (0.166 before it: the decode reading alone does not
# refuse float8; the prompt's and all five layer limits do).


def spec(cfg, **mutate) -> Dict[str, Any]:
    """What the equations take from the configuration, as plain values; a
    test's mutation overrides one of them."""
    s = {
        "window": cfg.window_size,
        "theta": cfg.rope_theta,
        "rotate": {"window": (cfg.window_position or cfg.position) == "rope",
                   "full": cfg.position == "rope"},
        "gate": cfg.attn_output_gate,
        "qk_norm": cfg.qk_norm == "head",
        "post_norms": cfg.sandwich_norm,
        "embed_scale": cfg.embed_multiplier,
        "top_k": cfg.moe_top_k,
        "score": cfg.moe_score_func,
        "norm_topk": cfg.moe_norm_topk_prob,
        "norm_eps_sum": cfg.moe_norm_topk_eps,
        "routed_scale": cfg.moe_routed_scale,
        "shared": bool(cfg.moe_shared_experts),
        "bias_in_gate": False,
        # the precisions the configuration states for the router's product
        # and for the scores' softmax; a test's mutation narrows one
        "router_dtype": F32,
        "softmax_dtype": F32,
        "held": (cfg.moe_expert_first,
                 cfg.moe_experts_held or cfg.num_experts),
        "eps": cfg.norm_eps,
        "heads": cfg.num_heads,
        "hd": cfg.dims_per_head,
    }
    s.update(mutate)
    return s


def _check(cfg):
    bad = []
    if cfg.layer_pattern is None:
        bad.append("no layer_pattern")
    if cfg.norm != "rmsnorm" or cfg.activation != "swiglu":
        bad.append(f"norm={cfg.norm}, activation={cfg.activation}")
    if cfg.position != "none" or cfg.window_position != "rope" \
            or cfg.rope_interleaved or cfg.rotary_dim:
        bad.append("a position rule other than rotary (whole head, "
                   "half-split pairs) on window layers and none on full")
    if (cfg.attn_bias or cfg.mlp_bias or cfg.lm_head_bias
            or cfg.qk_norm != "head" or not cfg.attn_output_gate
            or not cfg.sandwich_norm or cfg.window_attn_sink
            or cfg.window_kv_heads or cfg.window_rope_theta
            or cfg.v_head_dim or cfg.attn_value_scale != 1.0
            or cfg.parallel_residual or cfg.post_layernorm or cfg.norm_after
            or cfg.shared_layernorm or cfg.embed_layernorm
            or not cfg.final_norm or not cfg.causal or cfg.tie_embeddings
            or cfg.attn_softmax_scale is not None or cfg.moe_drop_tokens
            or cfg.moe_use_residual or cfg.attention_layers is not None
            or cfg.moe_shared_experts != 1 or cfg.loop_passes != 1
            or cfg.residual_multiplier != 1.0
            or cfg.lm_head_multiplier != 1.0):
        bad.append("an option outside the afmoe block")
    if bad:
        raise NotImplementedError(
            "reference_afmoe.py covers the afmoe (Trinity) block only: "
            + ", ".join(bad))


def _rounded(x, dtype):
    """float32 ``x`` rounded to the values ``dtype`` holds and kept in
    float32: ``reduce_precision``, which the compiler may not drop as it may
    a cast down and back (``xla_allow_excess_precision``)."""
    if jnp.dtype(dtype) == jnp.dtype(F32):
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, positions, theta: float):
    """x [S, H, hd]: every dim rotated, pairs (i, i + hd/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]           # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(s, kind: str, lp, a, positions):
    """Normed activations a [S, d] -> the attention branch's output [S, d]
    BEFORE N2 (projections, QK-norm, the kind's position rule and mask, the
    gate, Wo)."""
    S = a.shape[0]
    H, hd = s["heads"], s["hd"]
    q = (a @ lp["wq"]).reshape(S, H, hd)
    Hkv = lp["wk"].shape[-1] // hd
    k = (a @ lp["wk"]).reshape(S, Hkv, hd)
    v = (a @ lp["wv"]).reshape(S, Hkv, hd)
    if s["qk_norm"]:
        q = _rmsnorm(q, lp["q_norm_scale"], s["eps"])
        k = _rmsnorm(k, lp["k_norm_scale"], s["eps"])
    if s["rotate"][kind]:
        q = rotary(q, positions, s["theta"])
        k = rotary(k, positions, s["theta"])
    back = positions[:, None] - positions[None, :]                # i - j
    ok = back >= 0
    if kind == "window":
        ok = ok & (back < s["window"])
    G = H // Hkv
    out = []
    for g in range(Hkv):        # a KV head's query heads at a time: the
        qs = q[:, g * G:(g + 1) * G]     # [S, S] scores of all 48 do not fit
        sc = jnp.einsum("qhd,kd->hqk", qs, k[:, g]) / math.sqrt(hd)
        sc = _rounded(jnp.where(ok[None], sc, -jnp.inf), s["softmax_dtype"])
        out.append(jnp.einsum(
            "hqk,kd->qhd",
            _rounded(jax.nn.softmax(sc, axis=-1), s["softmax_dtype"]),
            v[:, g]))
    o = jnp.concatenate(out, axis=1).reshape(S, H * hd)
    if s["gate"]:
        o = o * jax.nn.sigmoid(a @ lp["wg"])
    return o @ lp["wo"]


def expert_weights(s, lp, m):
    """m [S, d] -> the gate of every expert for every token [S, E]: the
    chosen experts' gates, 0 for the rest."""
    rd = s["router_dtype"]
    z = _rounded(_rounded(m, rd) @ _rounded(lp["router"], rd), rd)
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
        gate = gate / (gate.sum(-1, keepdims=True) + s["norm_eps_sum"])
    return gate * s["routed_scale"]


def routed_experts(s, lp, m):
    """m [S, d] -> the held experts' part of sum_e gate_e expert_e(m)."""
    gate = expert_weights(s, lp, m)
    first, count = s["held"]
    out = jnp.zeros_like(m)
    for e in range(count):      # lp's stacks hold experts first .. first+count
        g = m @ lp["w_gate"][e].astype(F32)
        u = m @ lp["w_up"][e].astype(F32)
        out = out + gate[:, first + e:first + e + 1] * (
            (g * jax.nn.sigmoid(g) * u) @ lp["w_down"][e].astype(F32))
    return out


def gated_mlp(lp, m, prefix=""):
    g = m @ lp[prefix + "w_gate"]
    return ((g * jax.nn.sigmoid(g) * (m @ lp[prefix + "w_up"]))
            @ lp[prefix + "w_down"])


def expert_layer(s, lp, m):
    """m [S, d] -> the shared expert (whole) + this share's routed part."""
    f = routed_experts(s, lp, m)
    return f + gated_mlp(lp, m, "shared_") if s["shared"] else f


def attention_branch(s, kind, lp, x, positions):
    """x -> N2(attention(N1(x))): what the residual stream gains."""
    y = attention(s, kind, lp, _rmsnorm(x, lp["attn_norm_scale"], s["eps"]),
                  positions)
    return (_rmsnorm(y, lp["attn_post_norm_scale"], s["eps"])
            if s["post_norms"] else y)


def mlp_branch(s, dense, lp, x):
    """x -> N4(mlp(N3(x)))."""
    m = _rmsnorm(x, lp["mlp_norm_scale"], s["eps"])
    f = gated_mlp(lp, m) if dense else expert_layer(s, lp, m)
    return (_rmsnorm(f, lp["mlp_post_norm_scale"], s["eps"])
            if s["post_norms"] else f)


def _block(s, kind, dense, lp, x, positions):
    x = x + attention_branch(s, kind, lp, x, positions)
    return x + mlp_branch(s, dense, lp, x)


def layers(cfg):
    """``(group, index in the group, kind, dense)`` for each layer in the
    order run, from the first ``num_layers`` entries of ``layer_pattern``
    and ``dense_layers`` alone."""
    seen: Dict[str, int] = {}
    out = []
    for i, kind in enumerate(cfg.layer_pattern[:cfg.num_layers]):
        dense = i < cfg.dense_layers
        group = f"{kind}_{'dense' if dense else 'moe'}"
        out.append((group, seen.get(group, 0), kind, dense))
        seen[group] = seen.get(group, 0) + 1
    return out


_STACKS = ("w_gate", "w_up", "w_down")


def _layer(params, group: str, i: int, round_to=None) -> Dict[str, Any]:
    """One layer's leaves in float32, an expert layer's three stacks as
    they are stored (the loop casts one expert at a time).  ``round_to``: a
    dtype every weight is rounded through first (the next precision down)."""
    def f32(a):
        return (a.astype(round_to) if round_to is not None else a).astype(F32)

    stacked = "router" in params["layers"][group]
    return {k: (v[i] if round_to is None else v[i].astype(round_to))
            if stacked and k in _STACKS else f32(v[i])
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
        x = params["embed"].astype(F32)[tokens] * s["embed_scale"]
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


def _tick_positions(window: int, n_tokens: int, page: int) -> np.ndarray:
    """The positions the ``*_tick`` checks query, one a slot: inside the
    first page, a page's last row, under the window, its last row inside,
    the first past it (the ring has wrapped), a ring's whole turn further,
    the block's last."""
    ring = -(-window // page) + 1
    at = [3, page - 1, window // 2, window - 1, window, window + page + 1,
          ring * page + 7, n_tokens - 1]
    return np.unique(np.clip(at, 0, n_tokens - 1))


def _tick_rows(system, cfg, g, kind: str, lp, h, page: int):
    """The attention sublayer as a decode TICK runs it, through N2: a slot a
    position of :func:`_tick_positions`, each holding the sequence's K/V up
    to its position where the engine would have laid them (a full layer: the
    slot's pages in order; a window layer: logical page ``j`` in page ``j %
    ring`` of the slot's ring, the pages the window has left behind
    overwritten), read by the system's own plan and walk (``_paged_read_plan``
    / ``_ring_read_plan``, ``_attention_paged``).  No expert is routed here,
    so nothing flips: the tick's read is held as tightly as a prompt's."""
    n_tokens, window = h.shape[1], cfg.window_size
    at = _tick_positions(window, n_tokens, page)
    positions = jnp.arange(n_tokens, dtype=jnp.int32)[None]
    q, k, v = jax.jit(lambda lp, h: system._qkv(g, lp, h, positions))(lp, h)
    k, v = np.asarray(k[0].astype(F32)), np.asarray(v[0].astype(F32))
    per_slot = (-(-window // page) + 1 if kind == "window"
                else -(-n_tokens // page))
    pools = {n: np.zeros((1 + len(at) * per_slot, page) + a.shape[1:],
                         np.float32) for n, a in (("k", k), ("v", v))}
    for b, last in enumerate(at):       # oldest first: a ring's page keeps
        p = np.arange(last + 1)         # the newest positions laid in it
        rows = 1 + b * per_slot + (p // page) % per_slot, p % page
        pools["k"][rows], pools["v"][rows] = k[p], v[p]
    table = jnp.asarray(1 + np.arange(len(at) * per_slot, dtype=np.int32
                                      ).reshape(len(at), per_slot))

    def tick(lp, h, q, pools):
        start = jnp.asarray(at, jnp.int32)
        mask = jnp.ones((len(at), 1), bool)
        plan = (system._ring_read_plan(table, start, mask, page, window)
                if kind == "window"
                else system._paged_read_plan(table, start, mask, page))
        a = system._attention_paged(g, q[0, at][:, None], pools, plan)
        gate = system._attn_gate(g, lp, h[0, at][:, None])
        return system._norm(g, system._attn_out(g, lp, a, gate=gate),
                            lp["attn_post_norm_scale"])[:, 0]

    return jax.jit(tick)(lp, h, q, {n: jnp.asarray(a, h.dtype)
                                    for n, a in pools.items()})


def layer_checks(cfg, params, seed: int, n_tokens: Optional[int] = None,
                 mutate: Optional[Dict[str, Any]] = None, round_to=None,
                 page: int = 128) -> Dict[str, Dict[str, float]]:
    """The system's sublayers ALONE against this file's, on the last layer
    of each group (the larger reading where two groups share a check) and
    one seeded ``[1, n_tokens, d]`` activation (normal, unit variance: what a
    norm hands on), in the weights' own dtype on the system's side:
    ``{check: {"rel_err", "tol"}}``.  ``n_tokens`` defaults to a quarter
    more than the window in whole chunks of 512 (5,120 at the published
    4,096): the last fifth of the queries have lost keys to the window, and
    the block is one the prefill walks in chunks.

    ``window_attention`` / ``full_attention``: the attention sublayer as a
    prompt's prefill runs it (projections, the QK-norm, the kind's position
    rule, the window as the prefill walks it, the gate, Wo), through N2.
    ``window_tick`` / ``full_tick``: the same sublayer as a decode tick runs
    it (:func:`_tick_rows`: slots under, at and past the window read through
    the ring and the pages of ``page`` rows), against the same rows of this
    file's attention.
    ``expert_layer``: run as the paged forward runs it, through N4: the
    expert leaves the group's whole ``[n*E, ...]`` stack with this layer's
    experts at their offset, the shared expert beside them, the last eighth
    of the tokens masked (their rows are left out of the reading: the shared
    expert computes every row).

    ``mutate`` (a test's) changes this file's side (:func:`spec`);
    ``round_to`` rounds this file's weights and activation through a
    narrower dtype.  Either must push a check past its limit."""
    from deepspeed_tpu.models import transformer as system

    _check(cfg)
    s = spec(cfg, **(mutate or {}))
    if n_tokens is None:
        n_tokens = -(-(5 * cfg.window_size // 4) // 512) * 512
    dtype = params["embed"].dtype
    h = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (1, n_tokens, cfg.hidden_size)).astype(np.float32)).astype(dtype)
    positions = jnp.arange(n_tokens, dtype=jnp.int32)
    n_live = n_tokens - n_tokens // 8
    live = positions < n_live
    groups = system.layer_groups(cfg)
    h_ref = (h[0].astype(round_to) if round_to is not None
             else h[0]).astype(F32)
    out: Dict[str, Dict[str, float]] = {}

    def record(name, got, want, tol):
        # the larger of the groups' readings where two groups share a check
        err = max(layer_rel_err(got, want),
                  out.get(name, {}).get("rel_err", 0.0))
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
            gate = system._attn_gate(g, lp, h)
            if kind == "window":
                a = system._attention_window_block(
                    g, q, k, v, positions[None], cfg.window_size)
            else:
                a = system._attention_causal_block(g, q, k, v,
                                                   positions[None])
            return system._norm(g, system._attn_out(g, lp, a, gate=gate),
                                lp["attn_post_norm_scale"])[0]

        def reference_attention(lp, y):
            a = attention(s, kind, lp, y, positions)
            return _rmsnorm(a, lp["attn_post_norm_scale"], s["eps"])

        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference_attention)(lp_ref, h_ref)
        tol = WINDOW_ATTN_REL_TOL if kind == "window" else FULL_ATTN_REL_TOL
        record(f"{kind}_attention", jax.jit(system_attention)(leaves, h),
               want, tol)
        record(f"{kind}_tick", _tick_rows(system, cfg, g, kind, {
            k: v[i] for k, v in leaves.items()
            if k not in system._EXPERT_LEAVES or dense}, h, page)
            , want[_tick_positions(cfg.window_size, n_tokens, page)], tol)
        if dense:
            continue
        held = g.moe_experts_held or g.num_experts

        def system_experts(leaves, h):
            lp = {k: v.reshape(-1, *v.shape[2:])
                  if k in system._EXPERT_LEAVES else v[i]
                  for k, v in leaves.items()}
            f = system._mlp(g, lp, h, jax.random.PRNGKey(0),
                            deterministic=True, token_mask=live[None],
                            expert_offset=jnp.int32(i * held))[0]
            return system._norm(g, f, lp["mlp_post_norm_scale"])[0]

        def reference_experts(lp, y):
            return _rmsnorm(expert_layer(s, lp, y),
                            lp["mlp_post_norm_scale"], s["eps"])

        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference_experts)(lp_ref, h_ref)
        record("expert_layer",
               jax.jit(system_experts)(leaves, h)[:n_live], want[:n_live],
               EXPERT_LAYER_REL_TOL)
    return out
