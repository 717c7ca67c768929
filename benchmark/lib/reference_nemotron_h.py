"""A plain float32 reference of the Nemotron-H decoder as Nemotron-3-Super
runs it (``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16`` ``config.json``,
``model_type`` ``nemotron_h``), independent of
``deepspeed_tpu/models/transformer.py``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
chunks, no cache, no pages, no batching, no kernels, one sequence, the
recurrence ONE POSITION AT A TIME.  Every layer is ONE sublayer behind ONE
RMSNorm ``N`` (eps 1e-5, a learned scale) and ONE add, ``f`` by the letter
the layer has in ``hybrid_override_pattern``; no bias but the convolution's,
no multiplier anywhere::

    x_0    = Embed[id]
    x_l+1  = x_l + f_l(N_l(x_l))
    logits = N_f(x_L) W_head                                  (untied)

    f = M (Mamba-2), n [S, 4096]:
          [z | xBC | dt] = n W_in      (8,192 | 8,192 + 2 x 8 x 128 | 128)
          xBC = silu(conv1d_depthwise_causal(xBC; 4 taps) + b_conv)
          x (128 heads x 64), B, C (8 groups x 128) = split(xBC)
          dt_h = softplus(dt_h + dt_bias_h),  A_h = -exp(A_log_h)
          head h reads the B and C of group h // 16
          S_h,t = exp(dt_h,t A_h) S_h,t-1 + dt_h,t x_h,t (x) B_g(h),t
          y_h,t = S_h,t C_g(h),t + D_h x_h,t
          g = y * silu(z), RMS-normed WITHIN each group's 1,024 channels,
          times one learned scale of 8,192;  f = g W_out
    f = * (attention): q = n W_q (32 x 128), k = n W_k, v = n W_v (2 KV
          heads x 128), NO rotation and no position of any kind, causal
          softmax(q k^T / sqrt(128)) v, W_o
    f = E (experts in a latent):
          s = sigmoid(n W_r), float32, over all 512 experts (W_r on the full
          4,096); the 22 largest of s + b chosen (b in the choice alone;
          ties: the lower index); gate_e = s_e / (sum of the chosen s +
          1e-20) * 5
          u = n W_in' (4,096 -> 1,024);  expert_e(u) = relu(u W1_e)^2 W2_e
          f = (sum over the chosen e HELD HERE of gate_e expert_e(u)) W_out'
              (1,024 -> 4,096)  +  relu(n W1_s)^2 W2_s     (5,376 wide)

``held = (first, count)`` is one chip's share of the routed experts
(``moe_expert_first``, ``moe_experts_held``): the choice and the gates are
over all 512, and what an absent expert would add is left out, in the
program and here alike; ``held=(0, all)`` is the uncut layer.

Departures from the published description, each also in the configuration
file's ``assumed``: the multi-token-prediction module
(``num_nextn_predict_layers`` 1, ``mtp_hybrid_override_pattern`` ``*E``) is
LEFT OUT: how it joins the last state and the next token's embedding is in
neither ``config`` nor the catalog's description, and the main stack's logits
do not depend on it; ``e_score_correction_bias`` is not a checkpoint's but
drawn from the seed (``init_params``: the quantiles of a normal laid over
each share of the experts); weights are random from a seed.

It reads the parameter tree by the names ``init_params`` gives the leaves:
``layers/ssm_only/...`` stacked over the M layers, ``layers/mlp_moe/...``
over the E layers and ``layers/full_only/...`` over the * layers, each in the
order they appear in ``layer_pattern``.  The names are the interface, the
arithmetic is its own.  One layer's weights are upcast at a time, one
expert's inside a scan over the experts, and the head in column blocks, so
the float32 copies fit beside the system's bfloat16 weights on one chip.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_BLOCK = 16384      # columns of the head upcast at a time
GROUPS = {"ssm": "ssm_only", "mlp": "mlp_moe", "full": "full_only"}
_STACKS = ("w_in", "w_down")


# How the logits are read.  22 of 512 is a discrete choice: where the 22nd
# and 23rd of ``s + b`` lie closer than bfloat16 activations resolve them, the
# system and a float32 reference choose different experts, and with one expert
# in four here such a flip adds or removes one of a token's ~5.5 held experts.
# At the published widths on a v5e (my chip runs, PR 61: seeds 5100000011 and
# 5100000022, 3,000 prompt tokens and 48 decode tokens each; every token's
# max|diff| over the block's max|ref|, which is 6.1-6.4 x its root mean
# square) the readings are ONE population with a long tail, not two: median
# 0.011-0.012, 90th percentile 0.025-0.026, 95th 0.032-0.033, 98th 0.039-0.041,
# 99th 0.044, the worst of 3,000 0.057-0.070 (four runs); 48 decode tokens
# 0.011-0.015 at the median and 0.037-0.042 at the worst.  The reference
# against ITSELF with every layer's input rounded to bfloat16 shows the same
# tail (99th percentile 0.033-0.035, worst 0.048-0.067): it is bfloat16's
# through the routers, not a fault of the program's, and the kind's limit
# (0.05 of max|ref| on the plain maximum, set for dense models) lies inside
# it.  No limit is widened for it:
#
# A block ``[S, V]`` is read by its largest token after the worst ``S //
# FLIP_SHARE`` = one in twenty: 0.032-0.034 against the kind's 0.05, where
# float8_e4m3 weights read 0.49 on a single token.  What a fault on fewer than
# one token in twenty would do to a prompt (a page's edge, a chunk's) is held
# by ``layer_checks``, where both sides route ONE activation, nothing flips
# and every token is read.
FLIP_SHARE = 20
# A single token (a decode step) cannot leave itself out: it is read against
# ``FLIP_ROOM`` x the block's scale, so that the kind's 0.05 stands at 0.125 of
# max|ref|: twice the worst single token of ~12,000 read (0.070), a quarter of
# what float8_e4m3 weights read on one (0.49 of the token's own maximum, ~0.34
# on this scale); a tick that reads another slot's page reads over 0.5
# (PERF.md, PR 21).
FLIP_ROOM = 2.5
# max|logit| of a 3,000-token block in units of its root mean square (6.08 and
# 6.37 measured; 4.1-4.5 over one token's): every reading is taken against
# 6.2 x rms, so that a decode step and a prompt are read on one scale.
PEAK_OVER_RMS = 6.2


def rel_err(got, want) -> float:
    """The largest |got - want| of a token's logits over ``PEAK_OVER_RMS`` x
    the reference's root mean square: of a block ``[S, V]`` the largest
    after the worst ``S // FLIP_SHARE`` tokens; of one token (``[V]``) as it
    is, over ``FLIP_ROOM`` x that scale (see both).  Any other shape:
    :func:`layer_rel_err`."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if want.ndim > 2 or got.shape != want.shape:
        return layer_rel_err(got, want)
    scale = PEAK_OVER_RMS * float(np.sqrt(np.mean(want * want)))
    if want.ndim == 1:
        return float(np.abs(got - want).max() / (FLIP_ROOM * scale))
    per_token = np.sort(np.abs(got - want).max(-1))
    return float(per_token[len(per_token) - 1 - len(per_token) // FLIP_SHARE]
                 / scale)


def layer_rel_err(got, want) -> float:
    """max|got - want| / max|want| on a layer's own output:
    ``lib/reference.py``'s reading."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def state_rel_err(got, want) -> float:
    """A recurrent state read whole: the root of sum (got - want)^2 over sum
    want^2 (``reference_falcon_h1.py``'s reading, and its reason: over a
    million elements it repeats from seed to seed where the largest single
    element's error swings)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.sqrt(np.square(got - want).sum() / np.square(want).sum()))


# Single layers of the system against this file's, one of each letter, at the
# published widths on a v5e (my chip runs, PR 61: ten seeds as shipped, one
# each departure; PERF.md section 6).  Each limit lies between the largest
# the shipped system gives over its seeds (bfloat16 weights and activations,
# float32 state and router product, against this file's float32) and what
# comes of the named departure, with room on both sides.
#   One M layer (norm, mixer, add) over a padded prompt (700 real tokens in a
#   1,024 block: five scan chunks of 128 crossed, the real tokens ending
#   inside the sixth), a seeded activation of the embedding's scale,
#   max|diff| / max|ref| on what the layer adds: as shipped 0.0046-0.0056;
#   this file's own arithmetic with weights and activation rounded through
#   float8_e4m3 0.118.
MIXER_LAYER_REL_TOL = 0.02
#   The * layer likewise (no position, scores / sqrt(128)): as shipped
#   0.0028-0.0050; in float8_e4m3 0.076.
ATTN_LAYER_REL_TOL = 0.02
#   One expert layer ALONE (router, latent in, the 128 held experts, latent
#   out, the shared expert) on seeded rows of a norm's scale, system and
#   reference fed the SAME bfloat16 rows so that both route one activation
#   (behind the layer's own norm the two inputs differ by a bfloat16
#   rounding, which alone flips a few tokens in a hundred: the first form of
#   this check read 0.048-0.050).  22 of 512 is a discrete choice nearer to
#   ties than 8 of 64: the reference names the tokens whose 22nd and 23rd of
#   s + b lie within ``TIE_MARGIN`` of each other, where two float32 products
#   that sum in another order may choose differently and both are right
#   (float32 sums of 4,096 terms differ by ~1e-7 here).  Those tokens are
#   left out of ``expert_layer`` and COUNTED: ``router_near_tie_share`` read
#   1 to 4 tokens of 700 (0.0014-0.0057) and is held to 14
#   (``NEAR_TIE_SHARE_TOL``).  Every other token is read: as shipped
#   0.0031-0.0040; **the router's product rounded to bfloat16 0.041** (a
#   ``reduce_precision``: the 22nd expert of the tokens whose margin lies
#   within that rounding swapped); in float8_e4m3 0.106.  A flipped pair is
#   so reported, by the check it fails, and no limit is widened to let it
#   through.
EXPERT_LAYER_REL_TOL = 0.012
TIE_MARGIN = 1e-5
NEAR_TIE_SHARE_TOL = 0.02
#   The slot's state after the paged prefill of the 700 tokens in a 1,024
#   bucket against this file's state after position 699, the FIRST M layer
#   (layer 0: no router stands before it) by ``state_rel_err``: as shipped
#   0.0052-0.0060; a state kept in bfloat16 0.0092; in float8_e4m3 0.108.
PREFILL_STATE_REL_TOL = 0.02
#   The state and the logits after 256 teacher-forced decode steps behind
#   that prompt, through the state rows and the * layer's pages; the state
#   read over layer 0's slowest heads (``slow_heads``;
#   ``reference_falcon_h1.py`` has the reason): the check a state kept in
#   bfloat16 has to fail.  As shipped 0.0051-0.0060; ``state_dtype=
#   jnp.bfloat16`` on this file's side 0.0127 (the narrowest room of the
#   seven: 1.5 x above the largest shipped reading, 1.4 x under the bfloat16
#   one); in float8_e4m3 0.123.  The logits of the last step by
#   :func:`rel_err`'s single-token reading (behind five routers: flips
#   reach it): as shipped 0.0035-0.0070 (0.013-0.025 of the token's own
#   maximum); in float8_e4m3 0.13 (0.49).
DECODE_STATE_REL_TOL = 0.009
DECODE_LOGITS_REL_TOL = 0.05
CHECK_PROMPT, CHECK_BLOCK, CHECK_DECODE = 700, 1024, 256
TOY_CHECK = (45, 64, 40)    # the same three at the toy widths: chunks of 8
# The limits are measured where they judge, at the published widths.  At the
# CPU rehearsal's toy widths the same bfloat16 roundings are spread over a
# few hundred elements instead of a million and a reading swings with the
# seed: a model under 1,024 hidden channels is read against three times each
# limit.
TOY_HIDDEN, TOY_ROOM = 1024, 3.0


def plan(cfg) -> List[Tuple[str, int]]:
    """``(group, index in the group)`` of each layer run, in order: the
    first ``num_layers`` entries of the published pattern, ``"ssm"`` an M
    layer (group ``ssm_only``), ``"mlp"`` an E layer (``mlp_moe``),
    ``"full"`` a * layer (``full_only``)."""
    seen: Dict[str, int] = {}
    out = []
    for kind in cfg.layer_pattern[:cfg.num_layers]:
        group = GROUPS[kind]
        out.append((group, seen.get(group, 0)))
        seen[group] = seen.get(group, 0) + 1
    return out


def spec(cfg, **mutate) -> Dict[str, Any]:
    """What the equations take from the configuration, as plain values; a
    test's mutation overrides one of them."""
    held = cfg.moe_experts_held or cfg.num_experts
    s = {
        "eps": cfg.norm_eps, "heads": cfg.num_heads,
        "kv_heads": cfg.kv_heads, "hd": cfg.dims_per_head,
        "ssm_heads": cfg.ssm_heads, "ssm_p": cfg.ssm_head_dim,
        "ssm_n": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
        "taps": cfg.ssm_conv, "top_k": cfg.moe_top_k,
        "held": (cfg.moe_expert_first, held),
        "routed_scale": cfg.moe_routed_scale,
        "topk_eps": cfg.moe_norm_topk_eps,
        # what a test turns off to show that it matters: the groups of the
        # gated norm (1: one norm over all channels, Granite's), the square
        # on the ReLU, the two latent projections ("identity": the experts
        # read the input's first channels and write them back), the shared
        # expert, the routed experts, the add between two layers (False: an
        # E layer behind an M or * layer reads that layer's INPUT, the
        # parallel block)
        "norm_groups": cfg.ssm_groups, "square": True, "latent": "projected",
        "shared": True, "routed": True, "one_add": True,
        # the dtype the recurrent state is kept in between two positions,
        # and the one the router's logits are rounded through
        "state_dtype": F32, "router_dtype": F32,
    }
    s.update(mutate)
    return s


def slow_heads(lp) -> np.ndarray:
    """The quarter of a layer's state-space heads (at least one) whose state
    decays slowest at the step their bias alone gives: the smallest
    ``exp(A_log) * softplus(dt_bias)``."""
    rate = (np.exp(np.asarray(lp["ssm_A_log"], np.float64))
            * np.log1p(np.exp(np.asarray(lp["ssm_dt_bias"], np.float64))))
    return np.argsort(rate)[:max(1, len(rate) // 4)]


def _check(cfg):
    bad = []
    pattern = tuple(cfg.layer_pattern or ())[:cfg.num_layers]
    if not getattr(cfg, "one_sublayer", False) or not pattern or any(
            k not in GROUPS for k in pattern):
        bad.append("no layer_pattern of one-sublayer ssm, mlp and full "
                   "layers")
    if cfg.norm != "rmsnorm" or cfg.activation != "relu2":
        bad.append(f"norm={cfg.norm}, activation={cfg.activation}")
    if cfg.position != "none":
        bad.append(f"position={cfg.position} (the attention never rotates)")
    if (cfg.attn_bias or cfg.mlp_bias or cfg.qk_norm or cfg.embed_layernorm
            or not cfg.final_norm or not cfg.causal or cfg.tie_embeddings
            or cfg.attn_softmax_scale is not None or cfg.dense_layers
            or cfg.kv_lora_rank or cfg.attention_layers is not None
            or cfg.v_head_dim not in (None, cfg.dims_per_head)
            or cfg.moe_score_func != "sigmoid" or not cfg.moe_norm_topk_prob
            or not cfg.moe_select_bias or cfg.moe_drop_tokens
            or cfg.moe_shared_experts < 1 or not cfg.moe_latent_size
            or tuple(cfg.ssm_multipliers) != (1.0,) * 5
            or (cfg.embed_multiplier, cfg.lm_head_multiplier,
                cfg.residual_multiplier, cfg.ssm_in_multiplier,
                cfg.ssm_out_multiplier) != (1.0,) * 5):
        bad.append("an option outside the nemotron_h block")
    if bad:
        raise NotImplementedError(
            "reference_nemotron_h.py covers the Nemotron-H block only: "
            + ", ".join(bad))


def _rounded(x, dtype):
    """``x`` rounded through ``dtype`` and back, by an op the compiler may
    not take out (it is free to skip a convert and its inverse)."""
    if dtype == F32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def attention(s, lp, n):
    """n [S, d] (the normed input) -> attention's output [S, d]: no
    rotation, scores over the root of a head's width."""
    S = n.shape[0]
    H, Hkv, hd = s["heads"], s["kv_heads"], s["hd"]
    q = (n @ lp["wq"]).reshape(S, H, hd)
    k = jnp.repeat((n @ lp["wk"]).reshape(S, Hkv, hd), H // Hkv, axis=1)
    v = jnp.repeat((n @ lp["wv"]).reshape(S, Hkv, hd), H // Hkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    ok = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd) @ lp["wo"]


def mixer(s, lp, n, keep: Sequence[int] = ()):
    """n [S, d] (the normed input) -> ``(the mixer's output [S, d], the
    state [H, P, N] after each position of ``keep``)``: the recurrence one
    position at a time, head h on the B and C of group ``h // (H / G)``."""
    S = n.shape[0]
    H, P, N, G, K = (s["ssm_heads"], s["ssm_p"], s["ssm_n"], s["ssm_groups"],
                     s["taps"])
    ds, gn = H * P, G * N
    p = n @ lp["ssm_in"]
    z, xbc, dt = p[:, :ds], p[:, ds:2 * ds + 2 * gn], p[:, 2 * ds + 2 * gn:]
    # causal depthwise convolution: tap K - 1 meets the position itself
    ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    xbc = _silu(sum(ext[k:k + S] * lp["ssm_conv_w"][k] for k in range(K))
                + lp["ssm_conv_b"])
    x = xbc[:, :ds].reshape(S, H, P)
    B = jnp.repeat(xbc[:, ds:ds + gn].reshape(S, G, N), H // G, axis=1)
    C = jnp.repeat(xbc[:, ds + gn:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])
    A = -jnp.exp(lp["ssm_A_log"])
    sd = s["state_dtype"]

    def step(state, at):
        x_t, b_t, c_t, dt_t = at                # [H,P], [H,N], [H,N], [H]
        state = (jnp.exp(dt_t * A)[:, None, None] * state.astype(F32)
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = (state * c_t[:, None, :]).sum(-1)
        return state.astype(sd), y

    state, ys, kept, lo = jnp.zeros((H, P, N), sd), [], [], 0
    for hi in sorted(set(int(k) + 1 for k in keep) | {S}):
        if hi > lo:
            state, y = jax.lax.scan(step, state, (x[lo:hi], B[lo:hi],
                                                  C[lo:hi], dt[lo:hi]))
            ys.append(y)
        if hi - 1 in keep:
            kept.append(state.astype(F32))
        lo = hi
    y = jnp.concatenate(ys) + lp["ssm_D"][:, None] * x
    # gated, then normed within each group of channels
    g = (y.reshape(S, ds) * _silu(z)).reshape(S, s["norm_groups"], -1)
    g = g / jnp.sqrt((g * g).mean(-1, keepdims=True) + s["eps"])
    return (g.reshape(S, ds) * lp["ssm_norm_scale"]) @ lp["ssm_out"], kept


def _act(s, h):
    r = jnp.maximum(h, 0.0)
    return r * r if s["square"] else r


def route(s, lp, n):
    """n [S, d] -> ``(chosen experts [S, k], their gates [S, k], the margin
    [S] between the k-th and the (k+1)-th of s + b)``."""
    k = s["top_k"]
    score = jax.nn.sigmoid(_rounded(n @ lp["router"], s["router_dtype"]))
    top, idx = jax.lax.top_k(score + lp["router_bias"], k + 1)
    idx = idx[:, :k]                            # ties: the lower index
    chosen = jnp.take_along_axis(score, idx, axis=1)
    gates = (chosen / (chosen.sum(-1, keepdims=True) + s["topk_eps"])
             * s["routed_scale"])
    return idx, gates, top[:, k - 1] - top[:, k]


def experts(s, lp, n):
    """n [S, d] -> ``(the routed experts' part of the layer held here
    through the latent + the shared expert's, the router's margin [S])``.
    ``lp``'s expert stacks are as stored (one expert is upcast at a time);
    everything else of ``lp`` is float32."""
    first, held = s["held"]
    idx, gates, margin = route(s, lp, n)
    out = jnp.zeros_like(n)
    if s["routed"]:
        dl = lp["moe_latent_in"].shape[1]
        project = s["latent"] == "projected"
        u = n @ lp["moe_latent_in"] if project else n[:, :dl]

        def one(acc, at):
            e, w_in, w_down = at
            gate = jnp.where(idx == e, gates, 0.0).sum(-1)  # 0: not chosen
            return acc + gate[:, None] * (
                _act(s, u @ w_in.astype(F32)) @ w_down.astype(F32)), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
            first + jnp.arange(held), lp["w_in"], lp["w_down"]))
        out = (y @ lp["moe_latent_out"] if project
               else jnp.pad(y, ((0, 0), (0, n.shape[1] - dl))))
    if s["shared"]:
        out = out + _act(s, n @ lp["shared_w_in"]) @ lp["shared_w_down"]
    return out, margin


def block(s, lp, x, keep: Sequence[int] = (), read=None):
    """One layer, ``x + f(N(x))``: ``(its output [S, d], the mixer's states
    at ``keep`` (an M layer's; [] for any other), the router's margin (an E
    layer's; None for any other))``.  Which letter it is shows in its
    leaves.  ``read``: what the norm reads where it is not ``x`` (a test's
    ``one_add=False``)."""
    read = x if read is None else read
    if "ssm_in" in lp:
        f, kept = mixer(s, lp, _rmsnorm(read, lp["attn_norm_scale"],
                                        s["eps"]), keep)
        return x + f, kept, None
    if "wq" in lp:
        return x + attention(s, lp, _rmsnorm(
            read, lp["attn_norm_scale"], s["eps"])), [], None
    f, margin = experts(s, lp, _rmsnorm(read, lp["mlp_norm_scale"], s["eps"]))
    return x + f, [], margin


@functools.lru_cache(maxsize=None)
def _jitted_block(spec_items, keep):
    """:func:`block` under one ``jax.jit`` a specification, so that a second
    sequence of the same length compiles nothing."""
    s = dict(spec_items)
    return jax.jit(lambda lp, x, read: block(s, _f32(lp), x, keep, read))


def _run_block(s, keep=()):
    return _jitted_block(tuple(sorted(s.items())), tuple(keep))


def _layer(params, group: str, i: int, round_to=None) -> Dict[str, Any]:
    """Layer ``i`` of ``group``, its leaves as they are stored (``_f32``
    upcasts them inside the jitted block, where no float32 copy has to be
    written out); ``round_to``: a dtype every weight is rounded through
    first (the next precision down)."""
    return {k: v[i] if round_to is None else v[i].astype(round_to)
            for k, v in params["layers"][group].items()}


def _f32(lp):
    """Everything but the expert stacks, which :func:`experts` upcasts an
    expert at a time."""
    return {k: v if k in _STACKS else v.astype(F32) for k, v in lp.items()}


def _logits(s, params, x, round_to=None):
    """The final norm and the untied head over ``x [S, d]``, the head upcast
    ``HEAD_BLOCK`` columns at a time, each block of logits to the host as it
    is made."""
    x = _rmsnorm(x, params["final_norm_scale"].astype(F32), s["eps"])
    if round_to is not None:
        x = x.astype(round_to).astype(F32)
    head = params["lm_head"]
    out = []
    for c in range(0, head.shape[1], HEAD_BLOCK):
        w = head[:, c:c + HEAD_BLOCK]
        if round_to is not None:
            w = w.astype(round_to)
        out.append(np.asarray(jnp.dot(x, w.astype(F32))))
    return np.concatenate(out, axis=-1)


def forward(cfg, params, tokens, keep: Sequence[int] = (), round_to=None,
            rows: Optional[Sequence[int]] = None, **mutate):
    """tokens [S] int -> ``(logits [S, V] float32 on the host (of the
    positions ``rows`` alone where given), every M layer's states after the
    positions of ``keep``: [M layers][len(keep)] of [H, P, N])``.  A layer
    at a time from the leaves as they are stored.  ``round_to``: a dtype
    every weight and every layer's input is rounded through."""
    _check(cfg)
    s = spec(cfg, **mutate)
    keep = tuple(int(k) for k in keep)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        run = _run_block(s, keep)
        states, before = [], x
        for group, i in plan(cfg):
            if round_to is not None:
                x = x.astype(round_to).astype(F32)
            # one_add=False: an E layer reads what the layer before it read
            read = x if s["one_add"] or group != "mlp_moe" else before
            before = x
            x, kept, _ = run(_layer(params, group, i, round_to), x, read)
            if group == "ssm_only":
                states.append(kept)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return _logits(s, params, x, round_to), states


def reference_logits(cfg, params, tokens, round_to=None, **mutate):
    """tokens [S] int -> logits [S, V] float32 (V the vocabulary slice the
    head holds)."""
    return forward(cfg, params, tokens, round_to=round_to, **mutate)[0]


def layer_checks(cfg, params, seed: int, n_prompt: Optional[int] = None,
                 block_tokens: Optional[int] = None,
                 n_decode: Optional[int] = None, page_size: int = 128,
                 mutate: Optional[Dict[str, Any]] = None,
                 round_to=None) -> Dict[str, Dict[str, float]]:
    """One layer of each letter of the system ALONE against this file's, in
    the weights' own dtype on the system's side: ``{check: {"rel_err",
    "tol"}}``.

    ``mixer_layer`` / ``attention_layer``: the last M layer and the last *
    layer, each as the system's own ``_block`` (its one norm, its one
    sublayer, its one add) over a seeded ``[1, block_tokens, d]`` activation
    of which ``n_prompt`` positions are real: the system's chunked scan
    against this file's recurrence, its masked product against this file's.
    ``expert_layer``: the first E layer's expert layer ALONE (the system's
    ``_mlp``: router, latent in, its sorted rows through the held experts,
    latent out, the shared expert) on seeded rows of a norm's scale, both
    sides fed the SAME rows so that both route one activation; it reads
    every token but those the reference names as near ties (see
    ``TIE_MARGIN``), whose share is ``router_near_tie_share``.  The E
    layer's own norm and add are held by the logits.  ``state_after_prefill``: ``n_prompt``
    seeded tokens padded to ``block_tokens`` through the system's paged
    prefill into slot 1 of 3; layer 0's state row against this file's state
    after position ``n_prompt - 1`` (:func:`state_rel_err`).
    ``state_after_decode`` / ``logits_after_decode``: ``n_decode`` further
    tokens, teacher-forced one at a time through the system's paged decode
    step (the state rows, the * layer's pages), then layer 0's state (over
    its :func:`slow_heads`) and the last step's logits against this file's
    at the last position.

    ``mutate`` (a test's) changes this file's side (:func:`spec`);
    ``round_to`` rounds this file's weights and activations through a
    narrower dtype.  Either must push a check past its limit.  The three
    lengths default to ``CHECK_*`` (``TOY_CHECK`` under ``TOY_HIDDEN``
    hidden channels)."""
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models import transformer as system

    _check(cfg)
    mutate = mutate or {}
    s = spec(cfg, **mutate)
    toy = cfg.hidden_size < TOY_HIDDEN
    room = TOY_ROOM if toy else 1.0
    sizes = TOY_CHECK if toy else (CHECK_PROMPT, CHECK_BLOCK, CHECK_DECODE)
    n_prompt, block_tokens, n_decode = (
        b if a is None else a
        for a, b in zip((n_prompt, block_tokens, n_decode), sizes))
    dtype = params["embed"].dtype
    rng = np.random.default_rng(seed)
    out: Dict[str, Dict[str, float]] = {}
    layers = plan(cfg)
    groups = system.layer_groups(cfg)

    # -- one layer of each letter over a padded prompt
    h = jnp.asarray((rng.standard_normal((1, block_tokens, cfg.hidden_size))
                     * cfg.initializer_range).astype(np.float32)).astype(dtype)
    positions = jnp.arange(block_tokens, dtype=jnp.int32)[None]
    real = positions < n_prompt
    h_ref = (h[0, :n_prompt].astype(round_to) if round_to is not None
             else h[0, :n_prompt]).astype(F32)

    def system_layer(group, i):
        g = groups[group][0]

        def run(leaves, h):
            lp = {k: v[i] for k, v in leaves.items()}
            attend = mix = None
            if group == "ssm_only":
                mix = lambda lp, n: system._ssm_mixer(g, lp, n, real)  # noqa: E731
            elif group == "full_only":
                attend = system._attend_full(g, positions)
            return system._block(g, lp, h, positions, jax.random.PRNGKey(0),
                                 attend, token_mask=real, ssm=mix)[0][0]
        return jax.jit(run)(params["layers"][group], h)[:n_prompt]

    for name, group, tol in (
            ("mixer_layer", "ssm_only", MIXER_LAYER_REL_TOL),
            ("attention_layer", "full_only", ATTN_LAYER_REL_TOL)):
        i = max(j for g, j in layers if g == group)
        with jax.default_matmul_precision("highest"):
            want = _run_block(s)(_layer(params, group, i, round_to), h_ref,
                                 h_ref)[0]
        # the layer's own output: what it adds to the rows it was fed
        out[name] = {"rel_err": layer_rel_err(
            np.asarray(system_layer(group, i), np.float32)
            - np.asarray(h_ref), np.asarray(want) - np.asarray(h_ref)),
            "tol": room * tol}

    # -- the first E layer's expert layer ALONE (router, latent in, the held
    # experts, latent out, the shared expert) on rows of a norm's scale:
    # behind the layer's own norm the two sides' inputs would differ by a
    # bfloat16 rounding, and that alone flips the 22nd expert of some tokens
    g = groups["mlp_moe"][0]
    n = jnp.asarray(rng.standard_normal((1, block_tokens, cfg.hidden_size))
                    .astype(np.float32)).astype(dtype)
    n_ref = (n[0, :n_prompt].astype(round_to) if round_to is not None
             else n[0, :n_prompt]).astype(F32)
    got = jax.jit(lambda leaves, h: system._mlp(
        g, {k: v[0] for k, v in leaves.items()}, h, jax.random.PRNGKey(0),
        True, token_mask=real)[0][0])(params["layers"]["mlp_moe"],
                                      n)[:n_prompt]
    with jax.default_matmul_precision("highest"):
        want, margin = jax.jit(lambda lp, x: experts(s, _f32(lp), x))(
            _layer(params, "mlp_moe", 0, round_to), n_ref)
    sure = np.asarray(margin) > TIE_MARGIN
    # a share cannot be read finer than a token or two of the block
    out["router_near_tie_share"] = {
        "rel_err": float(1.0 - sure.mean()),
        "tol": max(NEAR_TIE_SHARE_TOL * room, 2.0 / n_prompt)}
    out["expert_layer"] = {
        "rel_err": layer_rel_err(np.asarray(got, np.float32)[sure],
                                 np.asarray(want)[sure]),
        "tol": room * EXPERT_LAYER_REL_TOL}

    # -- the slot's state through the paged prefill and the decode steps
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
    after_prefill = np.asarray(cache["ssm_state"][:, 1])
    for j in range(n_decode):
        logits, cache = step(params, toks[:, n_prompt + j:n_prompt + j + 1],
                             cache, jnp.full((1,), n_prompt + j, jnp.int32),
                             jnp.ones((1, 1), bool))
    after_decode = np.asarray(cache["ssm_state"][:, 1])
    want_logits, states = forward(
        cfg, params, toks[0], keep=(n_prompt - 1, total - 1),
        round_to=round_to, rows=(total - 1,), **mutate)

    # layer 0's state: no router stands before it, so what differs is the
    # state's own arithmetic and its inputs' rounding; the deeper layers
    # are held by the logits
    out["state_after_prefill"] = {
        "rel_err": state_rel_err(after_prefill[0], states[0][0]),
        "tol": room * PREFILL_STATE_REL_TOL}
    out["state_after_decode"] = {
        "rel_err": float(np.mean([
            state_rel_err(after_decode[0][hd], np.asarray(states[0][1])[hd])
            for hd in slow_heads(_layer(params, "ssm_only", 0))])),
        "tol": room * DECODE_STATE_REL_TOL}
    out["logits_after_decode"] = {
        "rel_err": rel_err(logits[0, 0], want_logits[0]),
        "tol": room * DECODE_LOGITS_REL_TOL}
    # untouched rows: the other slots' state stays zero
    out["other_slots_untouched"] = {
        "rel_err": float(np.abs(np.asarray(cache["ssm_state"][:, (0, 2)])
                                ).max()), "tol": 0.0}
    return out
