"""A plain float32 reference of the Granite 4.0-H decoder
(``ibm-granite/granite-4.0-h-small`` ``config.json``, ``model_type``
``granitemoehybrid``), independent of ``deepspeed_tpu/models/transformer.py``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
chunks, no cache, no pages, no batching, no kernels, one sequence, the
recurrence ONE POSITION AT A TIME.  ``n`` is an RMSNorm with a scale (eps
1e-5); no bias but the convolution's::

    x_0   = embedding_multiplier * Embed[id]
    a     = x + residual_multiplier * mix_l(n1(x))
    x'    = a + residual_multiplier * (moe(n2(a)) + shared(n2(a)))
    logits = n_f(x_L) Embed^T / logits_scaling          (tied)

    mix_l, layer_types[l] == "attention":
          q = W_q u (32 heads x 128), k = W_k u, v = W_v u (8 KV heads x
          128), NO rotation (position_embedding_type "nope"), causal
          softmax(q k^T * attention_multiplier) v, W_o
    mix_l, "mamba" (Mamba-2):
          [z | xBC | dt] = u W_in      (8,192 | 8,192 + 2 x 128 | 128)
          xBC = silu(conv1d_depthwise_causal(xBC; 4 taps) + b_conv)
          x (128 heads x 64), B, C (1 group x 128) = split(xBC)
          dt_h = softplus(dt_h + dt_bias_h),  A_h = -exp(A_log_h)
          S_h,t = exp(dt_h,t A_h) S_h,t-1 + dt_h,t x_h,t (x) B_t
          y_h,t = S_h,t C_t + D_h x_h,t
          out = W_out RMSNorm(y * silu(z); w_norm)     (one group: all 8,192)
    moe(u): logits = u W_r (72, float32); the 10 largest; gates = softmax
          over those 10; sum of gate_e * W_down,e(silu(W_gate,e u) * W_up,e u)
          over the chosen experts HELD HERE (``moe_experts_held`` from
          ``moe_expert_first``; the others' part is left out, in the
          program and here alike)
    shared(u): the same gated MLP of width 1,536, every token

It reads the parameter tree by the names ``init_params`` gives the leaves:
``layers/ssm_moe/...`` stacked over the mamba layers and ``layers/full_moe/
...`` over the attention layers, each in the order they appear in
``layer_pattern``.  The names are the interface, the arithmetic is its own.
One layer's weights are upcast at a time, one expert's inside a scan over
the experts, and the head in row blocks of the tied embedding, so the
float32 copies fit beside the system's bfloat16 weights on one chip.
Departures from the checkpoint are the configuration file's (``assumed``).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_BLOCK = 16384      # rows of the tied embedding upcast at a time


def rel_err(got, want) -> float:
    """max|got - want| / max|want|: ``lib/reference.py``'s reading."""
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


# Single pieces of the system against this file's, at the published widths
# on a v5e (my chip runs, PR 47: twelve seeds as shipped, three each departure;
# the table is in PERF.md section 6).  Each limit lies between the largest the
# shipped system gives over its seeds (bfloat16 weights and activations,
# float32 state and router, against this file's float32) and what comes of
# the named departure, with room on both sides.  No check but
# ``expert_layer`` has a router in its path: fed activations that differ by a
# bfloat16 rounding, system and reference choose another tenth expert for a
# few tokens in a hundred, and one such token moves a block's output by 0.01
# to 0.03 of its largest element whatever else is right (the first form of
# these checks read 0.007-0.008 and 0.017-0.023 for the two blocks and 0.018-
# 0.024 for a deeper layer's state, against 0.06 in float8: no room).
#   One mamba layer's block with the routed experts left out on both sides
#   (the system's own ``_block``: norm, mixer, residual multiplier, norm, the
#   shared expert as the gated MLP it is, residual multiplier) over a padded
#   prompt (700 real tokens in a 1,024 block: two scan chunks of 256 crossed,
#   the real tokens ending inside the third), a seeded activation of the
#   embedding's scale, max|diff| / max|ref|: as shipped 0.0053-0.0063; this
#   file's own arithmetic with weights and activation rounded through
#   float8_e4m3 0.059-0.067; without the shared expert 0.15-0.17.
STATE_BLOCK_REL_TOL = 0.02
#   The attention layer's block likewise (NoPE, scores x 1/128): as shipped
#   0.0044-0.0059; in float8_e4m3 0.055-0.062; without the shared expert
#   0.43-0.51.
ATTN_BLOCK_REL_TOL = 0.02
#   One expert layer ALONE (router, the 36 held experts, the shared expert)
#   on those rows, system and reference fed the same bfloat16 rows so that
#   both route alike: as shipped 0.0033-0.0043.  With the router's logits
#   rounded to bfloat16 (the tenth expert of the tokens whose tenth and
#   eleventh logits lie within that rounding swapped, every gate moved in its
#   third digit) 0.061-0.076; in float8_e4m3 0.13-0.16; without the shared
#   expert 4.7-5.3.
EXPERT_LAYER_REL_TOL = 0.012
#   The slot's state after the paged prefill of the 700 tokens in a 1,024
#   bucket against this file's state after position 699, the FIRST mamba
#   layer by ``state_rel_err``: as shipped 0.0049-0.0065; in float8_e4m3
#   0.096-0.121.
PREFILL_STATE_REL_TOL = 0.02
#   The state and the logits after teacher-forced decode steps behind that
#   prompt, the state read over the first mamba layer's slowest heads
#   (``slow_heads``; ``reference_falcon_h1.py`` has the reason): the check a
#   state kept in bfloat16 has to fail.  256 steps, the slowest quarter: as
#   shipped 0.0051-0.0066; ``state_dtype=jnp.bfloat16`` on this file's side
#   0.0110-0.0140 (the narrowest room of the six: 1.36 x above the largest
#   shipped reading, 1.22 x under the smallest bfloat16 one; the shipped
#   readings lie within 0.0009 of 0.0058); in float8_e4m3 0.107-0.130.  The
#   logits: as shipped
#   0.0011-0.0026; in float8_e4m3 0.0245-0.0263; without the shared expert
#   0.08-0.11.
DECODE_STATE_REL_TOL = 0.009
DECODE_LOGITS_REL_TOL = 0.008
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
    first ``num_layers`` entries of the published pattern, an ``"ssm"``
    entry a mamba layer (group ``ssm_moe``), ``"full"`` an attention layer
    (``full_moe``)."""
    seen: Dict[str, int] = {}
    out = []
    for kind in cfg.layer_pattern[:cfg.num_layers]:
        group = kind + "_moe"
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
        "embed_mult": cfg.embed_multiplier,
        "logits_mult": cfg.lm_head_multiplier,
        "attn_scale": cfg.attn_softmax_scale,
        "residual_mult": cfg.residual_multiplier,
        "ssm_heads": cfg.ssm_heads, "ssm_p": cfg.ssm_head_dim,
        "ssm_n": cfg.ssm_state, "taps": cfg.ssm_conv,
        "top_k": cfg.moe_top_k,
        "held": (cfg.moe_expert_first, held),
        "conv_bias": True, "skip_d": True, "shared": True, "routed": True,
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
    if not getattr(cfg, "ssm_heads", 0) or not pattern or any(
            k not in ("ssm", "full") for k in pattern):
        bad.append("no layer_pattern of ssm and full layers")
    if cfg.norm != "rmsnorm" or cfg.activation != "swiglu":
        bad.append(f"norm={cfg.norm}, activation={cfg.activation}")
    if cfg.position != "none":
        bad.append(f"position={cfg.position} (the attention never rotates)")
    if (cfg.attn_bias or cfg.mlp_bias or cfg.qk_norm or cfg.parallel_residual
            or cfg.post_layernorm or cfg.shared_layernorm
            or cfg.embed_layernorm or not cfg.final_norm or not cfg.causal
            or not cfg.tie_embeddings or cfg.attn_softmax_scale is None
            or cfg.dense_layers or cfg.kv_lora_rank
            or cfg.attention_layers is not None or cfg.ssm_groups != 1
            or cfg.v_head_dim not in (None, cfg.dims_per_head)
            or cfg.moe_score_func != "softmax" or not cfg.moe_norm_topk_prob
            or cfg.moe_select_bias or cfg.moe_routed_scale != 1.0
            or cfg.moe_drop_tokens or cfg.moe_shared_experts < 1
            or tuple(cfg.ssm_multipliers) != (1.0,) * 5
            or tuple(cfg.mlp_multipliers) != (1.0, 1.0)
            or (cfg.ssm_in_multiplier, cfg.ssm_out_multiplier,
                cfg.attn_in_multiplier, cfg.attn_out_multiplier,
                cfg.key_multiplier) != (1.0,) * 5):
        bad.append("an option outside the granitemoehybrid block")
    if bad:
        raise NotImplementedError(
            "reference_granite4h.py covers the Granite 4.0-H block only: "
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


def attention(s, lp, u):
    """u [S, d] (the normed input) -> attention's output [S, d]: no
    rotation, scores times ``attention_multiplier``."""
    S = u.shape[0]
    H, Hkv, hd = s["heads"], s["kv_heads"], s["hd"]
    q = (u @ lp["wq"]).reshape(S, H, hd)
    k = jnp.repeat((u @ lp["wk"]).reshape(S, Hkv, hd), H // Hkv, axis=1)
    v = jnp.repeat((u @ lp["wv"]).reshape(S, Hkv, hd), H // Hkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * s["attn_scale"]
    ok = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd) @ lp["wo"]


def mixer(s, lp, n, keep: Sequence[int] = ()):
    """n [S, d] (the normed input) -> ``(the mixer's output [S, d], the
    state [H, P, N] after each position of ``keep``)``: the recurrence one
    position at a time."""
    S = n.shape[0]
    H, P, N, K = s["ssm_heads"], s["ssm_p"], s["ssm_n"], s["taps"]
    ds = H * P
    p = n @ lp["ssm_in"]
    z, xbc, dt = p[:, :ds], p[:, ds:2 * ds + 2 * N], p[:, 2 * ds + 2 * N:]
    # causal depthwise convolution: tap K - 1 meets the position itself
    ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = sum(ext[k:k + S] * lp["ssm_conv_w"][k] for k in range(K))
    if s["conv_bias"]:
        conv = conv + lp["ssm_conv_b"]
    xbc = _silu(conv)
    x = xbc[:, :ds].reshape(S, H, P)
    B, C = xbc[:, ds:ds + N], xbc[:, ds + N:]            # one group: [S, N]
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])
    A = -jnp.exp(lp["ssm_A_log"])
    sd = s["state_dtype"]

    def step(state, at):
        x_t, b_t, c_t, dt_t = at
        state = (jnp.exp(dt_t * A)[:, None, None] * state.astype(F32)
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = (state * c_t[None, None, :]).sum(-1)
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
    y = jnp.concatenate(ys)
    if s["skip_d"]:
        y = y + lp["ssm_D"][:, None] * x
    g = _rmsnorm(y.reshape(S, ds) * _silu(z), lp["ssm_norm_scale"], s["eps"])
    return g @ lp["ssm_out"], kept


def gated_mlp(u, w_gate, w_up, w_down):
    return (_silu(u @ w_gate) * (u @ w_up)) @ w_down


def experts(s, lp, u):
    """u [S, d] -> the routed experts' part of the layer held here + the
    shared expert's.  ``lp``'s expert stacks are as stored (one expert is
    upcast at a time); everything else of ``lp`` is float32."""
    first, held = s["held"]
    out = jnp.zeros_like(u)
    if s["routed"]:
        logits = _rounded(u @ lp["router"], s["router_dtype"])
        top, idx = jax.lax.top_k(logits, s["top_k"])    # ties: lower index
        gates = jax.nn.softmax(top, axis=-1)

        def one(acc, at):
            e, w_gate, w_up, w_down = at
            gate = jnp.where(idx == e, gates, 0.0).sum(-1)  # 0: not chosen
            return acc + gate[:, None] * gated_mlp(
                u, w_gate.astype(F32), w_up.astype(F32),
                w_down.astype(F32)), None

        out, _ = jax.lax.scan(one, out, (
            first + jnp.arange(held), lp["w_gate"], lp["w_up"],
            lp["w_down"]))
    if s["shared"]:
        out = out + gated_mlp(u, lp["shared_w_gate"], lp["shared_w_up"],
                              lp["shared_w_down"])
    return out


def block(s, lp, x, keep: Sequence[int] = ()):
    """One layer: ``(its output [S, d], the mixer's states at ``keep`` (a
    mamba layer's; [] for an attention layer))``.  Which it is shows in its
    leaves."""
    n = _rmsnorm(x, lp["attn_norm_scale"], s["eps"])
    if "ssm_in" in lp:
        m, kept = mixer(s, lp, n, keep)
    else:
        m, kept = attention(s, lp, n), []
    a = x + s["residual_mult"] * m
    return (a + s["residual_mult"] * experts(
        s, lp, _rmsnorm(a, lp["mlp_norm_scale"], s["eps"])), kept)


_STACKS = ("w_gate", "w_up", "w_down")


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
    """The final norm and the tied head over ``x [S, d]``, the embedding
    upcast ``HEAD_BLOCK`` rows at a time, each block of logits to the host
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
        out.append(np.asarray(jnp.dot(x, w.astype(F32).T) * s["logits_mult"]))
    return np.concatenate(out, axis=-1)


def forward(cfg, params, tokens, keep: Sequence[int] = (), round_to=None,
            rows: Optional[Sequence[int]] = None, **mutate):
    """tokens [S] int -> ``(logits [S, V] float32 on the host (of the
    positions ``rows`` alone where given), every mamba layer's states after
    the positions of ``keep``: [mamba layers][len(keep)] of [H, P, N])``.
    A layer at a time from the leaves as they are stored.  ``round_to``: a
    dtype every weight and every layer's input is rounded through."""
    _check(cfg)
    s = spec(cfg, **mutate)
    keep = tuple(int(k) for k in keep)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32) * s["embed_mult"]
        run = jax.jit(lambda lp, x: block(s, _f32(lp), x, keep))
        states = []
        for group, i in plan(cfg):
            if round_to is not None:
                x = x.astype(round_to).astype(F32)
            x, kept = run(_layer(params, group, i, round_to), x)
            if group == "ssm_moe":
                states.append(kept)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return _logits(s, params, x, round_to), states


def reference_logits(cfg, params, tokens, round_to=None, **mutate):
    """tokens [S] int -> logits [S, V] float32 (V the vocabulary slice the
    embedding holds)."""
    return forward(cfg, params, tokens, round_to=round_to, **mutate)[0]


def layer_checks(cfg, params, seed: int, n_prompt: Optional[int] = None,
                 block_tokens: Optional[int] = None,
                 n_decode: Optional[int] = None, page_size: int = 128,
                 mutate: Optional[Dict[str, Any]] = None,
                 round_to=None) -> Dict[str, Dict[str, float]]:
    """Pieces of the system ALONE against this file's, in the weights' own
    dtype on the system's side: ``{check: {"rel_err", "tol"}}``.

    ``state_layer_block`` / ``attention_layer_block``: the last mamba layer
    and the last attention layer, each as the system's whole block with the
    ROUTED experts left out on both sides (its one mixer, both norms, both
    residual multipliers, the shared expert) over a seeded ``[1,
    block_tokens, d]`` activation of which ``n_prompt`` positions are real:
    the system's chunked scan against this file's recurrence, its masked
    product against this file's.  ``expert_layer``: the first layer's expert
    layer alone (router, held experts, shared expert) on those rows, both
    sides fed the same rows.  ``state_after_prefill``: ``n_prompt`` seeded
    tokens padded to ``block_tokens`` through the system's paged prefill
    into slot 1 of 3; the first mamba layer's state row against this file's
    state after position ``n_prompt - 1`` (:func:`state_rel_err`).
    ``state_after_decode`` / ``logits_after_decode``: ``n_decode`` further
    tokens, teacher-forced one at a time through the system's paged decode
    step (the state rows, the attention layer's page), then the first mamba
    layer's state (over its :func:`slow_heads`) and the last step's logits
    against this file's at the last position.

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

    # -- one block of each kind, and one expert layer, over a padded prompt
    scale = float(cfg.initializer_range * cfg.embed_multiplier)
    h = jnp.asarray((rng.standard_normal((1, block_tokens, cfg.hidden_size))
                     * scale).astype(np.float32)).astype(dtype)
    positions = jnp.arange(block_tokens, dtype=jnp.int32)[None]
    real = positions < n_prompt
    h_ref = (h[0, :n_prompt].astype(round_to) if round_to is not None
             else h[0, :n_prompt]).astype(F32)

    def system_block(group, i):
        g = groups[group][0]

        def run(leaves, h):
            # the system's whole block with the routed experts left out: its
            # expert layer finds no router and runs the shared expert as the
            # gated MLP it is
            lp = {k: v[i] for k, v in leaves.items()
                  if k != "router" and k not in _STACKS}
            lp.update({k: lp.pop("shared_" + k) for k in _STACKS})
            attend, mix = ((None, lambda lp, n: system._ssm_mixer(
                g, lp, n, real)) if group == "ssm_moe" else
                (system._attend_full(g, positions), None))
            return system._block(g, lp, h, positions, jax.random.PRNGKey(0),
                                 attend, token_mask=real, ssm=mix)[0][0]
        return jax.jit(run)(params["layers"][group], h)[:n_prompt]

    unrouted = dict(s, routed=False)
    for name, group, tol in (
            ("state_layer_block", "ssm_moe", STATE_BLOCK_REL_TOL),
            ("attention_layer_block", "full_moe", ATTN_BLOCK_REL_TOL)):
        i = max(j for g, j in layers if g == group)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda lp, x: block(unrouted, _f32(lp), x)[0])(
                _layer(params, group, i, round_to), h_ref)
        out[name] = {"rel_err": rel_err(system_block(group, i), want),
                     "tol": room * tol}

    group, i = layers[0]
    g = groups[group][0]
    got = jax.jit(lambda leaves, h: system._mlp(
        g, {k: v[i] for k, v in leaves.items()}, h, jax.random.PRNGKey(0),
        True, token_mask=real)[0][0])(params["layers"][group], h)[:n_prompt]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda lp, x: experts(s, _f32(lp), x))(
            _layer(params, group, i, round_to), h_ref)
    out["expert_layer"] = {"rel_err": rel_err(got, want),
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

    # the FIRST mamba layer's state: no router stands before it, so what
    # differs is the state's own arithmetic and its inputs' rounding.  A
    # deeper layer's input has passed expert layers whose tenth expert
    # flips on a rounding for a few tokens in a hundred, which moves its
    # state by 0.02 whatever the state is kept in; the deeper layers are
    # held by the logits
    out["state_after_prefill"] = {
        "rel_err": state_rel_err(after_prefill[0], states[0][0]),
        "tol": room * PREFILL_STATE_REL_TOL}
    out["state_after_decode"] = {
        "rel_err": float(np.mean([
            state_rel_err(after_decode[0][hd], np.asarray(states[0][1])[hd])
            for hd in slow_heads(_layer(params, "ssm_moe", 0))])),
        "tol": room * DECODE_STATE_REL_TOL}
    out["logits_after_decode"] = {
        "rel_err": rel_err(logits[0, 0], want_logits[0]),
        "tol": room * DECODE_LOGITS_REL_TOL}
    # untouched rows: the other slots' state stays zero
    out["other_slots_untouched"] = {
        "rel_err": float(np.abs(np.asarray(cache["ssm_state"][:, (0, 2)])
                                ).max()), "tol": 0.0}
    return out
