"""A plain float32 reference of the Falcon-H1 decoder
(``tiiuae/Falcon-H1-34B-Instruct`` ``config.json``, ``model_type``
``falcon_h1``), independent of ``deepspeed_tpu/models/transformer.py``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
chunks, no cache, no pages, no batching, one sequence, the recurrence ONE
POSITION AT A TIME.  Every block, RMSNorm (eps 1e-5), no biases but the
convolution's::

    n   = RMSNorm(x; w_in)
    a   = Attn(n * attention_in_multiplier) * attention_out_multiplier
    s   = SSM(n) * ssm_out_multiplier
    h   = x + a + s
    y   = h + MLP(RMSNorm(h; w_ff))
    MLP(u) = W_down(silu(W_gate u * mlp_multipliers[0]) * W_up u)
             * mlp_multipliers[1]
    Attn : q = W_q u (heads x 128), k = (W_k u) * key_multiplier, v = W_v u
           (KV heads x 128); rotary on all 128 dims, half-split pairs (i,
           i + 64), theta 1e11; causal softmax(q k^T / sqrt(128)) v; W_o
    SSM(n): p = W_in (n * ssm_in_multiplier), its segments [z | x | B | C |
           dt] each times its ssm_multipliers entry
           xBC = silu(conv1d_depthwise(xBC; 4 taps, causal, with bias))
           dt_h = softplus(dt_h + dt_bias_h),  A_h = -exp(A_log_h)
           S_h,t = exp(dt_h,t A_h) S_h,t-1 + dt_h,t x_h,t (x) B_g(h),t
           y_h,t = S_h,t C_g(h),t + D_h x_h,t           (g(h) = h // 16)
           out = W_out(RMSNorm_grouped(y * silu(z); w_norm, 2 groups))
    tokens: e = Embed[id] * embedding_multiplier
            logits = W_head RMSNorm(y_L; w_f) * lm_head_multiplier

It reads the parameter tree by the names ``init_params`` gives the leaves
(``layers/...`` stacked over the layers): the names are the interface, the
arithmetic is its own.  One layer's weights are upcast at a time and the
head is applied in column blocks, so the float32 copies fit beside the
system's bfloat16 weights on one chip.  Departures from the checkpoint are
the configuration file's (``assumed``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_BLOCK = 32768      # columns of the head upcast at a time


def rel_err(got, want) -> float:
    """max|got - want| / max|want|: ``lib/reference.py``'s reading, which
    the dense bfloat16 paged path passes at 0.014-0.015."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def state_rel_err(got, want) -> float:
    """A recurrent state read whole: the root of sum (got - want)^2 over sum
    want^2.  Over a million elements a layer it repeats from seed to seed to
    a few percent of itself, where the largest single element's error, read
    against the largest element, swings by a factor of two (0.0052-0.0095
    for the shipped system over sixteen seeds, against 0.0049-0.0054 read
    so)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.sqrt(np.square(got - want).sum() / np.square(want).sum()))


# Single pieces of the system against this file's, at the published widths
# on a v5e (my chip runs, PR 40; the table is in PERF.md section 6).  Each
# limit lies between the largest the shipped system gives over its seeds
# (bfloat16 weights and activations, float32 state, against this file's
# float32) and what comes of computing one precision down, with room on both
# sides.
#   One block's output over a padded prompt (300 real tokens in a 512 block,
#   the last layer, a seeded activation of the embedding's scale), max|diff|
#   / max|ref|: as shipped 0.0040-0.0062 (sixteen seeds); this file's own
#   arithmetic with weights and activation rounded through float8_e4m3
#   0.048 and 0.062.
BLOCK_REL_TOL = 0.017
#   The slot's state after the paged prefill of 300 tokens in a 512 bucket
#   (the chunked scan, chunk 128, the real tokens ending inside a chunk)
#   against this file's state after position 299, the worst layer by
#   ``state_rel_err``: as shipped 0.0059-0.0067 (eight seeds); in
#   float8_e4m3 0.102 and 0.104.
PREFILL_STATE_REL_TOL = 0.02
#   The state and the logits after 256 teacher-forced decode steps behind
#   that prompt.  The state is the check a lower precision has to fail, and
#   it is read where a state's precision shows: over the quarter of a
#   layer's heads that decay slowest (``slow_heads``: the smallest
#   exp(A_log) x softplus(dt_bias)), each head's ``state_rel_err``, their
#   mean, the worst layer.  A head that remembers hundreds of steps sums the
#   rounding of every one of them, while the bfloat16 rounding of its
#   inputs x, B, C and dt averages out over the same steps; a head that
#   forgets within a few steps shows the inputs' rounding alone.  With the
#   state float32 it reads 0.0025-0.0034 (eight seeds); with the state leaf
#   kept in bfloat16 (a scratch copy of the program: the leaf cast, every
#   step's result rounded as it is written back) 0.0129-0.0167 on the same
#   seeds; over the WHOLE state the same runs read 0.0047-0.0062 and
#   0.0087-0.0120, and by its largest element 0.0052-0.0095 and 0.016-0.047:
#   too close, or too unsteady, to put a limit between.  ``state_dtype=
#   jnp.bfloat16`` on this file's side reads the same from the other side;
#   in float8_e4m3 the whole state reads 0.103.  The logits hardly see the
#   state's precision (0.0045-0.0061 either way) and are held against the
#   next precision down: in float8_e4m3 0.074-0.091.
DECODE_STATE_REL_TOL = 0.0065
DECODE_LOGITS_REL_TOL = 0.03
CHECK_PROMPT, CHECK_BLOCK, CHECK_DECODE = 300, 512, 256
# The limits are measured where they judge, at the published widths.  At the
# CPU rehearsal's toy widths (a 4 x 8 x 16 state, 64 hidden channels) the
# same bfloat16 roundings are spread over a few hundred elements instead of
# a million and a reading swings with the seed (the state after 256 steps,
# over the toy's one slowest head, 0.0063-0.0077 there): a model under 1,024
# hidden channels is read against twice each limit.
TOY_HIDDEN, TOY_ROOM = 1024, 2.0


def spec(cfg, **mutate) -> Dict[str, Any]:
    """What the equations take from the configuration, as plain values; a
    test's mutation overrides one of them."""
    s = {
        "eps": cfg.norm_eps, "theta": cfg.rope_theta,
        "heads": cfg.num_heads, "kv_heads": cfg.kv_heads,
        "hd": cfg.dims_per_head,
        "embed_mult": cfg.embed_multiplier, "head_mult": cfg.lm_head_multiplier,
        "attn_in_mult": cfg.attn_in_multiplier,
        "attn_out_mult": cfg.attn_out_multiplier,
        "key_mult": cfg.key_multiplier,
        "ssm_in_mult": cfg.ssm_in_multiplier,
        "ssm_out_mult": cfg.ssm_out_multiplier,
        "ssm_mults": tuple(cfg.ssm_multipliers),     # z, x, B, C, dt
        "mlp_mults": tuple(cfg.mlp_multipliers),
        "ssm_heads": cfg.ssm_heads, "ssm_p": cfg.ssm_head_dim,
        "ssm_n": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
        "taps": cfg.ssm_conv,
        "norm_after_gate": True, "conv_bias": True, "skip_d": True,
        # the dtype the recurrent state is kept in between two positions
        "state_dtype": F32,
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
    if not getattr(cfg, "ssm_heads", 0):
        bad.append("no ssm_heads")
    if cfg.norm != "rmsnorm" or cfg.activation != "swiglu":
        bad.append(f"norm={cfg.norm}, activation={cfg.activation}")
    if (cfg.position != "rope" or cfg.rope_interleaved
            or cfg.rotary_dim not in (None, cfg.dims_per_head)):
        bad.append("rotary other than half-split over the whole head")
    if (cfg.attn_bias or cfg.mlp_bias or cfg.lm_head_bias or cfg.qk_norm
            or cfg.parallel_residual or cfg.post_layernorm
            or cfg.shared_layernorm or cfg.embed_layernorm
            or not cfg.final_norm or not cfg.causal or cfg.tie_embeddings
            or cfg.attn_softmax_scale is not None or cfg.num_experts != 1
            or cfg.layer_pattern is not None or cfg.dense_layers
            or cfg.kv_lora_rank or cfg.attention_layers is not None
            or cfg.v_head_dim not in (None, cfg.dims_per_head)):
        bad.append("an option outside the falcon_h1 block")
    if bad:
        raise NotImplementedError(
            "reference_falcon_h1.py covers the Falcon-H1 block only: "
            + ", ".join(bad))


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def rotary(x, positions, theta: float):
    """x [S, H, w]: all ``w`` dims rotated in half-split pairs (i, i + w/2)
    by ``position x theta ** (-2i / w)``."""
    w = x.shape[-1]
    inv = theta ** (-jnp.arange(0, w, 2, dtype=F32) / w)
    ang = positions.astype(F32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :w // 2], x[..., w // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(s, lp, u, positions):
    """u [S, d] (the normed input times attention_in_multiplier) -> the
    attention side's output [S, d] before its out-multiplier."""
    S = u.shape[0]
    H, Hkv, hd = s["heads"], s["kv_heads"], s["hd"]
    q = rotary((u @ lp["wq"]).reshape(S, H, hd), positions, s["theta"])
    k = rotary(((u @ lp["wk"]) * s["key_mult"]).reshape(S, Hkv, hd),
               positions, s["theta"])
    v = (u @ lp["wv"]).reshape(S, Hkv, hd)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    ok = positions[:, None] >= positions[None, :]
    p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd) @ lp["wo"]


def mixer(s, lp, n, keep: Sequence[int] = ()):
    """n [S, d] (the normed input) -> ``(the mixer's output [S, d] before
    its out-multiplier, the state [H, P, N] after each position of
    ``keep``)``: the recurrence one position at a time."""
    S = n.shape[0]
    H, P, N, G, K = (s["ssm_heads"], s["ssm_p"], s["ssm_n"], s["ssm_groups"],
                     s["taps"])
    ds, gn = H * P, G * N
    p = (n * s["ssm_in_mult"]) @ lp["ssm_in"]
    mz, mx, mb, mc, mdt = s["ssm_mults"]
    z = p[:, :ds] * mz
    xbc = jnp.concatenate([p[:, ds:2 * ds] * mx,
                           p[:, 2 * ds:2 * ds + gn] * mb,
                           p[:, 2 * ds + gn:2 * ds + 2 * gn] * mc], axis=-1)
    dt = p[:, 2 * ds + 2 * gn:] * mdt
    # causal depthwise convolution: tap K - 1 meets the position itself
    ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = sum(ext[k:k + S] * lp["ssm_conv_w"][k] for k in range(K))
    if s["conv_bias"]:
        conv = conv + lp["ssm_conv_b"]
    xbc = _silu(conv)
    x = xbc[:, :ds].reshape(S, H, P)
    B = jnp.repeat(xbc[:, ds:ds + gn].reshape(S, G, N), H // G, axis=1)
    C = jnp.repeat(xbc[:, ds + gn:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])
    A = -jnp.exp(lp["ssm_A_log"])
    sd = s["state_dtype"]

    def step(state, at):
        x_t, b_t, c_t, dt_t = at
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
    y = jnp.concatenate(ys)
    if s["skip_d"]:
        y = y + lp["ssm_D"][:, None] * x
    y = y.reshape(S, ds)

    def grouped_norm(a):
        a = a.reshape(S, G, ds // G)
        a = a / jnp.sqrt((a * a).mean(-1, keepdims=True) + s["eps"])
        return a.reshape(S, ds) * lp["ssm_norm_scale"]

    g = (grouped_norm(y * _silu(z)) if s["norm_after_gate"]
         else grouped_norm(y) * _silu(z))
    return g @ lp["ssm_out"], kept


def mlp(s, lp, u):
    m0, m1 = s["mlp_mults"]
    return ((_silu((u @ lp["w_gate"]) * m0) * (u @ lp["w_up"]))
            @ lp["w_down"]) * m1


def block(s, lp, x, positions, keep: Sequence[int] = ()):
    """One block: ``(its output [S, d], the mixer's states at ``keep``)``."""
    n = _rmsnorm(x, lp["attn_norm_scale"], s["eps"])
    a = attention(s, lp, n * s["attn_in_mult"], positions)
    m, kept = mixer(s, lp, n, keep)
    h = x + a * s["attn_out_mult"] + m * s["ssm_out_mult"]
    return h + mlp(s, lp, _rmsnorm(h, lp["mlp_norm_scale"], s["eps"])), kept


def _layer(params, i: int, round_to=None) -> Dict[str, Any]:
    """Layer ``i``'s leaves as they are stored (:func:`_f32` upcasts them
    inside the jitted block, where no float32 copy has to be written out);
    ``round_to``: a dtype every weight is rounded through first (the next
    precision down)."""
    return {k: v[i] if round_to is None else v[i].astype(round_to)
            for k, v in params["layers"].items()}


def _f32(lp):
    return {k: v.astype(F32) for k, v in lp.items()}


def _logits(cfg, s, params, x, round_to=None):
    """The final norm and the head over ``x [S, d]``, the head upcast
    ``HEAD_BLOCK`` columns at a time."""
    x = _rmsnorm(x, params["final_norm_scale"].astype(F32), s["eps"])
    if round_to is not None:
        x = x.astype(round_to).astype(F32)
    head = params["lm_head"]
    out = []
    for c in range(0, head.shape[1], HEAD_BLOCK):
        w = head[:, c:c + HEAD_BLOCK]
        if round_to is not None:
            w = w.astype(round_to)
        # each block to the host as it is made: [S, V] float32 is 0.76 GB
        # at 732 tokens, and its concatenation as much again
        out.append(np.asarray(jnp.dot(x, w.astype(F32)) * s["head_mult"]))
    return np.concatenate(out, axis=-1)


def forward(cfg, params, tokens, keep: Sequence[int] = (), round_to=None,
            rows: Optional[Sequence[int]] = None, **mutate):
    """tokens [S] int -> ``(logits [S, V] float32 on the host (of the
    positions ``rows`` alone where given), every layer's mixer states after
    the positions of
    ``keep``: [layers][len(keep)] of [H, P, N])``.  Each layer is run with
    its own weights upcast, a layer at a time from the leaves as they are
    stored.  ``round_to``: a dtype every weight and every layer's input is
    rounded through."""
    _check(cfg)
    s = spec(cfg, **mutate)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    keep = tuple(int(k) for k in keep)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32) * s["embed_mult"]
        run = jax.jit(lambda lp, x: block(s, _f32(lp), x, positions, keep))
        states = []
        for i in range(cfg.num_layers):
            if round_to is not None:
                x = x.astype(round_to).astype(F32)
            x, kept = run(_layer(params, i, round_to), x)
            states.append(kept)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return _logits(cfg, s, params, x, round_to), states


def reference_logits(cfg, params, tokens, round_to=None, **mutate):
    """tokens [S] int -> logits [S, V] float32."""
    return forward(cfg, params, tokens, round_to=round_to, **mutate)[0]


def layer_checks(cfg, params, seed: int, n_prompt: int = CHECK_PROMPT,
                 block_tokens: int = CHECK_BLOCK, n_decode: int = CHECK_DECODE,
                 page_size: int = 128, mutate: Optional[Dict[str, Any]] = None,
                 round_to=None) -> Dict[str, Dict[str, float]]:
    """Pieces of the system ALONE against this file's, in the weights' own
    dtype on the system's side: ``{check: {"rel_err", "tol"}}``.

    ``block_padded_prompt``: the last layer's block (both mixers, the MLP)
    over a seeded ``[1, block_tokens, d]`` activation of which ``n_prompt``
    positions are real, the system's chunked scan against this file's
    recurrence over the real ones.  ``state_after_prefill``: ``n_prompt``
    seeded tokens padded to ``block_tokens`` through the system's paged
    prefill into slot 1 of 3; every layer's state row against this file's
    state after position ``n_prompt - 1`` (:func:`state_rel_err`, the worst
    layer).
    ``state_after_decode`` / ``logits_after_decode``: ``n_decode`` further
    tokens, teacher-forced one at a time through the system's paged decode
    step, then the states (over each layer's :func:`slow_heads`) and the
    last step's logits against this file's at the last position: the check
    a state kept in a lower precision fails.

    ``mutate`` (a test's) changes this file's side (:func:`spec`);
    ``round_to`` rounds this file's weights and activations through a
    narrower dtype.  Either must push a check past its limit."""
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models import transformer as system

    _check(cfg)
    mutate = mutate or {}
    s = spec(cfg, **mutate)
    room = TOY_ROOM if cfg.hidden_size < TOY_HIDDEN else 1.0
    dtype = params["embed"].dtype
    rng = np.random.default_rng(seed)
    out: Dict[str, Dict[str, float]] = {}

    # -- one block over a padded prompt
    i = cfg.num_layers - 1
    scale = float(cfg.initializer_range * cfg.embed_multiplier)
    h = jnp.asarray((rng.standard_normal((1, block_tokens, cfg.hidden_size))
                     * scale).astype(np.float32)).astype(dtype)
    positions = jnp.arange(block_tokens, dtype=jnp.int32)
    real = (positions < n_prompt)[None]

    def system_block(leaves, h):
        lp = {k: v[i] for k, v in leaves.items()}
        return system._block(
            cfg, lp, h, positions[None], jax.random.PRNGKey(0),
            system._attend_full(cfg, positions[None]),
            ssm=lambda lp, n: system._ssm_mixer(cfg, lp, n, real))[0][0]

    got = jax.jit(system_block)(params["layers"], h)[:n_prompt]
    with jax.default_matmul_precision("highest"):
        h_ref = (h[0, :n_prompt].astype(round_to) if round_to is not None
                 else h[0, :n_prompt]).astype(F32)
        want = jax.jit(lambda lp, x: block(
            s, _f32(lp), x, positions[:n_prompt])[0])(
            _layer(params, i, round_to), h_ref)
    out["block_padded_prompt"] = {"rel_err": rel_err(got, want),
                                  "tol": room * BLOCK_REL_TOL}

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

    def layer_state(layer, k):
        return np.asarray(states[layer][k])

    out["state_after_prefill"] = {
        "rel_err": max(state_rel_err(after_prefill[layer],
                                     layer_state(layer, 0))
                       for layer in range(cfg.num_layers)),
        "tol": room * PREFILL_STATE_REL_TOL}
    out["state_after_decode"] = {
        "rel_err": max(float(np.mean([
            state_rel_err(after_decode[layer][h], layer_state(layer, 1)[h])
            for h in slow_heads(_layer(params, layer))]))
            for layer in range(cfg.num_layers)),
        "tol": room * DECODE_STATE_REL_TOL}
    out["logits_after_decode"] = {
        "rel_err": rel_err(logits[0, 0], want_logits[0]),
        "tol": room * DECODE_LOGITS_REL_TOL}
    # untouched rows: the other slots' state stays zero
    out["other_slots_untouched"] = {
        "rel_err": float(np.abs(np.asarray(cache["ssm_state"][:, (0, 2)])
                                ).max()), "tol": 0.0}
    return out
