"""A plain float32 reference of the Olmo-Hybrid decoder
(``allenai/Olmo-Hybrid-7B`` ``config.json``, ``model_type`` ``olmo_hybrid``),
independent of ``deepspeed_tpu/models/transformer.py``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
chunk form, no cache, no pages, no batching, no kernels, one sequence, the
delta rule ONE POSITION AT A TIME.  ``N`` is an RMSNorm with a scale (eps
1e-6); no bias anywhere::

    x_0    = Embed[id]
    a      = x + N1(mix_l(x))                 the norm on the branch's OUTPUT,
    x'     = a + N2(W_down(silu(W_gate a) * W_up a))          none on its input
    logits = N_f(x_L) W_head                                  (untied)

    mix_l, layer_types[l] == "full_attention":
          q = Nq(W_q x), k = Nk(W_k x) (RMSNorm with a scale over the WHOLE
          projection, before the head split), v = W_v x; 30 heads over 30 KV
          heads of 128, NO rotation, causal softmax(q k^T / sqrt(128)) v, W_o
    mix_l, "linear_attention" (Gated DeltaNet, arXiv:2412.06464), per head h
    of 30, keys 96 wide, values 192:
          [q | k | v | gate] = x W_in          (2,880 | 2,880 | 5,760 | 5,760)
          [b | a] = x W_ba                     (30 | 30)
          q, k, v = silu(conv1d_depthwise_causal([q | k | v]; 4 taps, no bias))
          q_h, k_h = q_h / sqrt(|q_h|^2 + 1e-6), k_h / sqrt(|k_h|^2 + 1e-6)
          q_h = q_h / sqrt(96)
          beta_h = 2 sigmoid(b_h)              (linear_allow_neg_eigval: the 2)
          alpha_h = exp(-exp(A_log_h) softplus(a_h + dt_bias_h))
          S_h = alpha_h S_h                    S_h [96, 192] float32, from zeros
          S_h = S_h + beta_h k_h (v_h - S_h^T k_h)^T
          o_h = S_h^T q_h
          out = W_out [RMSNorm_192(o_h; w_norm) * silu(gate)_h]_h

It reads the parameter tree by the names ``init_params`` gives the leaves:
``layers/linear_dense/...`` stacked over the delta layers and
``layers/full_dense/...`` over the attention layers, each in the order they
appear in ``layer_pattern``; ``delta_in`` is the four projections side by
side in the order above, ``delta_ba`` the two scalars a head, ``delta_conv_w``
the taps over q, k and v together.  The names are the interface, the
arithmetic is its own.  One layer's weights are upcast at a time and the head
in column blocks, so the float32 copies fit beside the system's bfloat16
weights on one chip.

Departures from the checkpoint, each also under ``assumed`` in the
configuration's file: (1) the wiring (norm after each branch, none before)
and the QK-norm are the Olmo 2 / Olmo 3 family's: ``config.json`` carries no
key for either; (2) ``rope_parameters.rope_theta`` null is read as NO rotary
embedding on the attention layers; (3) ``head_dim`` = 3,840 / 30 = 128, the
convolutions have no bias, ``A_log`` is drawn as log U(1, 16) and ``dt_bias``
as Mamba-2's (the inverse softplus of a log-uniform step in [1e-3, 1e-1]),
both norms of a block start at 1 / sqrt(2 x the layers of its kind), the
embedding rows are drawn at std 1 (a block with no norm on its input reads
them raw), every other weight normal with std 0.02.  Weights are random from ``--seed``, never
the checkpoint's.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_BLOCK = 16384      # columns of the head upcast at a time


def rel_err(got, want) -> float:
    """max|got - want| / max|want|: ``lib/reference.py``'s reading."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / np.abs(want).max())


def state_rel_err(got, want) -> float:
    """A recurrent state read whole: the root of sum (got - want)^2 over sum
    want^2 (``reference_falcon_h1.py``'s reading and its reason: over half a
    million elements it repeats from seed to seed where the largest single
    element's error swings)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.sqrt(np.square(got - want).sum() / np.square(want).sum()))


# Single pieces of the system against this file's, at the published widths
# on a v5e (my chip runs, PR 51: seventeen seeds as shipped, PERF.md section
# 6; two seeds each departure).  Each limit lies between the largest the shipped
# system gives over its seeds (bfloat16 weights and activations, float32
# state, against this file's float32) and what this file gives with weights
# and activations rounded through float8_e4m3, the nearest precision below,
# with room on both sides.
#   One delta layer's whole block (the system's own ``_block``: the mixer,
#   the norm behind it, the MLP, the norm behind it) over a padded prompt
#   (700 real tokens in a 1,024 block: ten scan chunks of 64 crossed, the
#   real tokens ending inside the eleventh), a seeded activation of unit
#   scale (the stream's), max|diff| / max|ref|: as shipped 0.0051-0.0061; in
#   float8_e4m3 0.060-0.071; with beta = sigmoid(b), the factor 2 left out,
#   0.040-0.050; with q and k not L2-normed the recurrence overflows (nan,
#   which passes no limit).
LINEAR_BLOCK_REL_TOL = 0.02
#   The attention layer's block likewise (QK-norm, no rotation): as shipped
#   0.0048-0.0062; in float8_e4m3 0.059-0.060.
ATTN_BLOCK_REL_TOL = 0.02
#   The slot's state after the paged prefill of the 700 tokens in a 1,024
#   bucket against this file's state after position 699, the FIRST delta
#   layer by ``state_rel_err``: as shipped 0.0042-0.0052; in float8_e4m3
#   0.114-0.126; without the factor 2 1.00-1.02.
PREFILL_STATE_REL_TOL = 0.02
#   The state and the logits after teacher-forced decode steps behind that
#   prompt, the state read over the first delta layer's slowest heads
#   (``slow_heads``): the check a state kept in bfloat16 has to fail.  256
#   steps, the slowest quarter: as shipped 0.0029-0.0032;
#   ``state_dtype=jnp.bfloat16`` on this file's side 0.0077-0.0080 (the
#   narrowest room of the five: 1.6 x above the largest shipped reading, 1.5
#   x under the smallest bfloat16 one); in float8_e4m3 0.129-0.131.  The
#   logits: as shipped 0.0115-0.0150; in float8_e4m3 0.238-0.268; without the
#   factor 2 0.180-0.188.
DECODE_STATE_REL_TOL = 0.005
DECODE_LOGITS_REL_TOL = 0.04
CHECK_PROMPT, CHECK_BLOCK, CHECK_DECODE = 700, 1024, 256
TOY_CHECK = (45, 64, 40)    # the same three at the toy widths: chunks of 8
# The limits are measured where they judge, at the published widths.  At the
# CPU rehearsal's toy widths the same bfloat16 roundings are spread over a
# few hundred elements instead of half a million and a reading swings with
# the seed: a model under 1,024 hidden channels is read against three times
# each limit.
TOY_HIDDEN, TOY_ROOM = 1024, 3.0

_GROUP = {"linear": "linear_dense", "full": "full_dense"}


def plan(cfg) -> List[Tuple[str, int]]:
    """``(group, index in the group)`` of each layer run, in order: the
    first ``num_layers`` entries of the published pattern."""
    seen: Dict[str, int] = {}
    out = []
    for kind in cfg.layer_pattern[:cfg.num_layers]:
        group = _GROUP[kind]
        out.append((group, seen.get(group, 0)))
        seen[group] = seen.get(group, 0) + 1
    return out


def spec(cfg, **mutate) -> Dict[str, Any]:
    """What the equations take from the configuration, as plain values; a
    test's mutation overrides one of them (``l2norm``, ``beta_scale``,
    ``gate_norm`` and ``conv_silu`` each drop one term)."""
    s = {
        "eps": cfg.norm_eps, "heads": cfg.num_heads, "hd": cfg.dims_per_head,
        "lin_heads": cfg.linear_heads, "dk": cfg.linear_key_dim,
        "dv": cfg.linear_value_dim, "taps": cfg.linear_conv,
        "beta_scale": 2.0 if cfg.linear_neg_eigval else 1.0,
        "l2norm": True, "gate_norm": True, "conv_silu": True,
        # the dtype the matrix state is kept in between two positions
        "state_dtype": F32,
    }
    s.update(mutate)
    return s


def slow_heads(lp) -> np.ndarray:
    """The quarter of a delta layer's heads (at least one) whose state
    decays slowest where the token adds nothing to the gate: the smallest
    ``exp(A_log) * softplus(dt_bias)``."""
    rate = (np.exp(np.asarray(lp["delta_A_log"], np.float64))
            * np.log1p(np.exp(np.asarray(lp["delta_dt_bias"], np.float64))))
    return np.argsort(rate)[:max(1, len(rate) // 4)]


def _check(cfg):
    bad = []
    pattern = tuple(cfg.layer_pattern or ())[:cfg.num_layers]
    if not getattr(cfg, "linear_heads", 0) or not pattern or any(
            k not in _GROUP for k in pattern):
        bad.append("no layer_pattern of linear and full layers")
    if cfg.norm != "rmsnorm" or cfg.activation != "swiglu":
        bad.append(f"norm={cfg.norm}, activation={cfg.activation}")
    if cfg.position != "none":
        bad.append(f"position={cfg.position} (the attention never rotates)")
    if (not getattr(cfg, "norm_after", False) or not cfg.qk_norm
            or cfg.attn_bias or cfg.mlp_bias or cfg.parallel_residual
            or cfg.post_layernorm or cfg.shared_layernorm
            or cfg.embed_layernorm or not cfg.final_norm or not cfg.causal
            or cfg.tie_embeddings or cfg.attn_softmax_scale is not None
            or cfg.dense_layers or cfg.kv_lora_rank or cfg.num_experts != 1
            or cfg.attention_layers is not None or cfg.ssm_heads
            or cfg.kv_heads != cfg.num_heads
            or cfg.v_head_dim not in (None, cfg.dims_per_head)
            or (cfg.embed_multiplier, cfg.lm_head_multiplier,
                cfg.residual_multiplier) != (1.0, 1.0, 1.0)):
        bad.append("an option outside the olmo_hybrid block")
    if bad:
        raise NotImplementedError(
            "reference_olmo_hybrid.py covers the Olmo-Hybrid block only: "
            + ", ".join(bad))


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def attention(s, lp, x):
    """x [S, d] -> attention's output [S, d]: QK-norm over the whole
    projections, no rotation, scores / sqrt(head width)."""
    S = x.shape[0]
    H, hd = s["heads"], s["hd"]
    q = _rmsnorm(x @ lp["wq"], lp["q_norm_scale"], s["eps"]).reshape(S, H, hd)
    k = _rmsnorm(x @ lp["wk"], lp["k_norm_scale"], s["eps"]).reshape(S, H, hd)
    v = (x @ lp["wv"]).reshape(S, H, hd)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    ok = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd) @ lp["wo"]


def delta_mixer(s, lp, x, keep: Sequence[int] = ()):
    """x [S, d] -> ``(the mixer's output [S, d], the state [H, dk, dv] after
    each position of ``keep``)``: the delta rule one position at a time."""
    S = x.shape[0]
    H, dk, dv, K = s["lin_heads"], s["dk"], s["dv"], s["taps"]
    p = x @ lp["delta_in"]
    qkv, gate = p[:, :2 * H * dk + H * dv], p[:, 2 * H * dk + H * dv:]
    ba = x @ lp["delta_ba"]
    b, a = ba[:, :H], ba[:, H:]
    # causal depthwise convolution: tap K - 1 meets the position itself
    ext = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), F32), qkv])
    qkv = sum(ext[k:k + S] * lp["delta_conv_w"][k] for k in range(K))
    if s["conv_silu"]:
        qkv = _silu(qkv)
    q = qkv[:, :H * dk].reshape(S, H, dk)
    k = qkv[:, H * dk:2 * H * dk].reshape(S, H, dk)
    v = qkv[:, 2 * H * dk:].reshape(S, H, dv)
    if s["l2norm"]:
        q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q = q / np.sqrt(dk)
    beta = s["beta_scale"] * jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(lp["delta_A_log"])
                    * jax.nn.softplus(a + lp["delta_dt_bias"]))
    sd = s["state_dtype"]

    def step(state, at):
        q_t, k_t, v_t, alpha_t, beta_t = at
        state = alpha_t[:, None, None] * state.astype(F32)
        u = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (
            beta_t[:, None] * (v_t - u))[:, None, :]
        return state.astype(sd), jnp.einsum("hkv,hk->hv", state, q_t)

    state, os_, kept, lo = jnp.zeros((H, dk, dv), sd), [], [], 0
    for hi in sorted(set(int(i) + 1 for i in keep) | {S}):
        if hi > lo:
            state, o = jax.lax.scan(step, state, (
                q[lo:hi], k[lo:hi], v[lo:hi], alpha[lo:hi], beta[lo:hi]))
            os_.append(o)
        if hi - 1 in keep:
            kept.append(state.astype(F32))
        lo = hi
    o = jnp.concatenate(os_)                                # [S, H, dv]
    if s["gate_norm"]:
        o = _rmsnorm(o, lp["delta_norm_scale"], s["eps"])
    return (o.reshape(S, H * dv) * _silu(gate)) @ lp["delta_out"], kept


def block(s, lp, x, keep: Sequence[int] = ()):
    """One layer: ``(its output [S, d], the mixer's states at ``keep`` (a
    delta layer's; [] for an attention layer))``.  Which it is shows in its
    leaves."""
    if "delta_in" in lp:
        m, kept = delta_mixer(s, lp, x, keep)
    else:
        m, kept = attention(s, lp, x), []
    a = x + _rmsnorm(m, lp["attn_norm_scale"], s["eps"])
    mlp = (_silu(a @ lp["w_gate"]) * (a @ lp["w_up"])) @ lp["w_down"]
    return a + _rmsnorm(mlp, lp["mlp_norm_scale"], s["eps"]), kept


def _layer(params, group: str, i: int, round_to=None) -> Dict[str, Any]:
    """Layer ``i`` of ``group``, its leaves as they are stored (upcast
    inside the jitted block); ``round_to``: a dtype every weight is rounded
    through first (the next precision down)."""
    return {k: v[i] if round_to is None else v[i].astype(round_to)
            for k, v in params["layers"][group].items()}


def _f32(lp):
    return {k: v.astype(F32) for k, v in lp.items()}


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
    positions ``rows`` alone where given), every delta layer's states after
    the positions of ``keep``: [delta layers][len(keep)] of [H, dk, dv])``.
    A layer at a time from the leaves as they are stored.  ``round_to``: a
    dtype every weight and every layer's input is rounded through."""
    _check(cfg)
    s = spec(cfg, **mutate)
    keep = tuple(int(k) for k in keep)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        run = jax.jit(lambda lp, x: block(s, _f32(lp), x, keep))
        states = []
        for group, i in plan(cfg):
            if round_to is not None:
                x = x.astype(round_to).astype(F32)
            x, kept = run(_layer(params, group, i, round_to), x)
            if group == _GROUP["linear"]:
                states.append(kept)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return _logits(s, params, x, round_to), states


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

    ``linear_layer_block`` / ``attention_layer_block``: the last delta layer
    and the last attention layer, each as the system's whole block over a
    seeded ``[1, block_tokens, d]`` activation of which ``n_prompt``
    positions are real: the system's chunk form against this file's
    recurrence, its masked product against this file's.
    ``state_after_prefill``: ``n_prompt`` seeded tokens padded to
    ``block_tokens`` through the system's paged prefill into slot 1 of 3; the
    first delta layer's state row against this file's state after position
    ``n_prompt - 1`` (:func:`state_rel_err`).  ``state_after_decode`` /
    ``logits_after_decode``: ``n_decode`` further tokens, teacher-forced one
    at a time through the system's paged decode step, then the first delta
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

    # -- one block of each kind over a padded prompt
    h = jnp.asarray(rng.standard_normal(
        (1, block_tokens, cfg.hidden_size)).astype(np.float32)).astype(dtype)
    positions = jnp.arange(block_tokens, dtype=jnp.int32)[None]
    real = positions < n_prompt
    h_ref = (h[0, :n_prompt].astype(round_to) if round_to is not None
             else h[0, :n_prompt]).astype(F32)

    def system_block(group, i):
        g = groups[group][0]

        def run(leaves, h):
            lp = {k: v[i] for k, v in leaves.items()}
            attend, mix = ((None, lambda lp, n: system._delta_mixer(
                g, lp, n, real)) if group == _GROUP["linear"] else
                (system._attend_full(g, positions), None))
            return system._block(g, lp, h, positions, jax.random.PRNGKey(0),
                                 attend, token_mask=real, ssm=mix)[0][0]
        return jax.jit(run)(params["layers"][group], h)[:n_prompt]

    for name, group, tol in (
            ("linear_layer_block", _GROUP["linear"], LINEAR_BLOCK_REL_TOL),
            ("attention_layer_block", _GROUP["full"], ATTN_BLOCK_REL_TOL)):
        i = max(j for g, j in layers if g == group)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda lp, x: block(s, _f32(lp), x)[0])(
                _layer(params, group, i, round_to), h_ref)
        out[name] = {"rel_err": rel_err(system_block(group, i), want),
                     "tol": room * tol}

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

    def first_layer_state(cache):
        return np.asarray(system.delta_state_heads(
            cfg, cache["delta_state"][0, 1]))

    after_prefill = first_layer_state(cache)
    for j in range(n_decode):
        logits, cache = step(params, toks[:, n_prompt + j:n_prompt + j + 1],
                             cache, jnp.full((1,), n_prompt + j, jnp.int32),
                             jnp.ones((1, 1), bool))
    after_decode = first_layer_state(cache)
    want_logits, states = forward(
        cfg, params, toks[0], keep=(n_prompt - 1, total - 1),
        round_to=round_to, rows=(total - 1,), **mutate)

    # the FIRST delta layer's state: what differs is the state's own
    # arithmetic and its inputs' rounding; the deeper layers are held by
    # the logits
    out["state_after_prefill"] = {
        "rel_err": state_rel_err(after_prefill, states[0][0]),
        "tol": room * PREFILL_STATE_REL_TOL}
    out["state_after_decode"] = {
        "rel_err": float(np.mean([
            state_rel_err(after_decode[hd], np.asarray(states[0][1])[hd])
            for hd in slow_heads(_layer(params, _GROUP["linear"], 0))])),
        "tol": room * DECODE_STATE_REL_TOL}
    out["logits_after_decode"] = {
        "rel_err": rel_err(logits[0, 0], want_logits[0]),
        "tol": room * DECODE_LOGITS_REL_TOL}
    # untouched rows: the other slots' state stays zero
    out["other_slots_untouched"] = {
        "rel_err": float(np.abs(np.asarray(
            cache["delta_state"][:, (0, 2)])).max()), "tol": 0.0}
    return out
