"""A plain float32 reference of the Ouro looped decoder
(``ByteDance/Ouro-2.6B`` ``config.json``, ``model_type`` ``ouro``; "Scaling
Latent Reasoning via Looped Language Models", 2025-10), independent of
``deepspeed_tpu/models/transformer.py``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
cache, no pages, no kernel, no batching, one sequence, every pass over the
whole sequence.  With ``R = total_ut_steps`` passes over ONE stack of ``L``
layers, every norm an RMSNorm (eps 1e-6, a scale and no offset), no bias::

    x = Embed[id]
    for r in 0 .. R-1:                          # the same weights every pass
      for l in 0 .. L-1:
        a = Attn_l(N1_l(x))                     # causal over THIS pass's keys
        x = x + N2_l(a W_o)
        u = N3_l(x)
        x = x + N4_l(W_down(silu(W_gate u) * (W_up u)))
      x = N_final(x)                            # after every pass
    logits = x W_head                           # after the last pass, untied
    Attn : q = W_q u, k = W_k u, v = W_v u (16 heads x 128 each; no grouping);
           rotary on all 128 dims of q and k, half-split pairs (i, i + 64),
           theta 1e6; softmax(q k^T / sqrt(128)) v over positions <= own

The keys and values of pass ``r`` are made from pass ``r``'s own x, so a
system that caches them keeps ``R x L`` caches; here nothing is cached and
each pass attends over what it has just computed.  The exit gate of the
published model (a linear map to one logit a pass) is not here: at the
published ``early_exit_threshold`` 1 every token runs all ``R`` passes and
the logits are the last pass's.

It reads the parameter tree by the names ``init_params`` gives the leaves
(``layers/...`` stacked over the layers): the names are the interface, the
arithmetic is its own.  One layer's weights are upcast at a time and the
head is applied in column blocks, so the float32 copies fit beside the
system's bfloat16 weights on one chip.  Departures from the checkpoint are
the configuration file's (``assumed``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_BLOCK = 16384      # columns of the head upcast at a time


def rel_err(got, want) -> float:
    """max|got - want| / max|want|: ``lib/reference.py``'s reading, which
    the dense bfloat16 paged path passes at 0.014-0.015."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def rms_rel_err(got, want) -> float:
    """An activation read whole: the root of sum (got - want)^2 over sum
    want^2.  x after a pass has just been normed, so every element is of
    order one and the largest single error over 2,048 of them swings with
    the seed where this does not."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.sqrt(np.square(got - want).sum() / np.square(want).sum()))


# Single pieces of the system against this file's, at the published widths
# on a v5e (my chip runs, PR 44; the table is in PERF.md section 6).  Each
# limit lies between the largest the shipped system gives over its seeds
# (bfloat16 weights and products, a float32 residual stream, against this
# file's float32) and the smallest of its counter-readings, with room on
# both sides: this file's weights and activations rounded through
# float8_e4m3, and the system's pool with two passes' rows exchanged
# (``tamper``) or every pass's rows replaced by pass 0's.
#   block_padded_prompt (layer 47's block over 300 real positions of 512):
#   as shipped 0.0040-0.0063 (seven seeds); in float8_e4m3 0.057-0.061.
#   pass_r_x (x after pass r at the last of 8 decode steps behind a prompt
#   of 200, through the paged path, read whole): as shipped 0.0195-0.0219
#   after pass 1 and 0.0113-0.0159 after passes 2-4 (seven seeds); in
#   float8_e4m3 0.84-1.10; passes 2 and 3 exchanged 0.148 (pass 3) and 0.084
#   (pass 4), passes 0 and 1 exchanged 0.69-1.16, one region for all four
#   0.37 / 0.66 / 0.89 after passes 2 / 3 / 4.
# With the residual stream in bfloat16 (this PR's first form) the same
# checks read 0.020 / 0.023 / 0.026 / 0.030 after the four passes and the
# logits 0.030-0.046 (eleven seeds) against the kind's 0.05: 384 roundings
# of x a token; carried in float32 the logits read 0.018-0.024.
BLOCK_REL_TOL = 0.02
PASS_X_REL_TOL = 0.05
CHECK_PROMPT, CHECK_BLOCK, CHECK_DECODE = 300, 512, 8
PASS_PROMPT, PASS_BUCKET = 200, 256
# The limits are measured where they judge, at the published widths.  At the
# CPU rehearsal's toy widths (64 hidden channels) the same bfloat16 roundings
# are spread over a few dozen elements instead of thousands and a reading
# swings with the seed: a model under 1,024 hidden channels is read against
# twice each limit.
TOY_HIDDEN, TOY_ROOM = 1024, 2.0


def spec(cfg, **mutate) -> Dict[str, Any]:
    """What the equations take from the configuration, as plain values; a
    test's mutation overrides one of them."""
    s = {
        "eps": cfg.norm_eps, "theta": cfg.rope_theta,
        "heads": cfg.num_heads, "hd": cfg.dims_per_head,
        "passes": cfg.loop_passes,
        # the norms after the branches (N2, N4), and the final norm after
        # every pass and not after the last alone
        "post_norms": True, "norm_every_pass": True,
    }
    s.update(mutate)
    return s


def _check(cfg):
    bad = []
    if not getattr(cfg, "sandwich_norm", False):
        bad.append("no sandwich_norm")
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
            or getattr(cfg, "ssm_heads", 0)
            or cfg.kv_heads != cfg.num_heads
            or cfg.v_head_dim not in (None, cfg.dims_per_head)):
        bad.append("an option outside the ouro block")
    if bad:
        raise NotImplementedError(
            "reference_ouro.py covers the Ouro block only: " + ", ".join(bad))


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


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
    """u [S, d] (the normed input) -> the attention branch [S, d] after
    ``W_o``, causal over the sequence's own positions."""
    S, H, hd = u.shape[0], s["heads"], s["hd"]
    q = rotary((u @ lp["wq"]).reshape(S, H, hd), positions, s["theta"])
    k = rotary((u @ lp["wk"]).reshape(S, H, hd), positions, s["theta"])
    v = (u @ lp["wv"]).reshape(S, H, hd)
    scores = jnp.einsum("shd,thd->hst", q, k) / math.sqrt(hd)
    seen = positions[None, :] <= positions[:, None]          # [S, T]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    out = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(S, H * hd) @ lp["wo"]


def block(s, lp, x, positions):
    """One layer over x [S, d], float32 leaves ``lp``."""
    eps = s["eps"]
    a = attention(s, lp, _rmsnorm(x, lp["attn_norm_scale"], eps), positions)
    if s["post_norms"]:
        a = _rmsnorm(a, lp["attn_post_norm_scale"], eps)
    x = x + a
    u = _rmsnorm(x, lp["mlp_norm_scale"], eps)
    m = (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]
    if s["post_norms"]:
        m = _rmsnorm(m, lp["mlp_post_norm_scale"], eps)
    return x + m


def _layer(params, i: int, round_to=None):
    """Layer ``i``'s leaves as they are stored (``round_to``: rounded
    through a narrower dtype first)."""
    lp = {k: v[i] for k, v in params["layers"].items()}
    if round_to is not None:
        lp = {k: v.astype(round_to) for k, v in lp.items()}
    return lp


def _f32(lp):
    return {k: v.astype(F32) for k, v in lp.items()}


def _logits(params, x, round_to=None):
    """The head over ``x [S, d]`` (normed already), upcast ``HEAD_BLOCK``
    columns at a time, each block to the host as it is made."""
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


def forward(cfg, params, tokens, round_to=None,
            rows: Optional[Sequence[int]] = None, **mutate):
    """tokens [S] int -> ``(logits [S, V] float32 on the host (of the
    positions ``rows`` alone where given), x after each pass [passes][S or
    len(rows), d])``: after the final norm where ``norm_every_pass`` puts
    one there, and after the last pass always.  Each layer is run with its
    own weights upcast, a layer at a time from the leaves as they are
    stored.  ``round_to``: a dtype every weight and every layer's input is
    rounded through."""
    _check(cfg)
    s = spec(cfg, **mutate)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    at = None if rows is None else jnp.asarray(rows)
    final = params["final_norm_scale"].astype(F32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        run = jax.jit(lambda lp, x: block(s, _f32(lp), x, positions))
        after = []
        for r in range(s["passes"]):
            for i in range(cfg.num_layers):
                if round_to is not None:
                    x = x.astype(round_to).astype(F32)
                x = run(_layer(params, i, round_to), x)
            if s["norm_every_pass"] or r == s["passes"] - 1:
                x = _rmsnorm(x, final, s["eps"])
            after.append(np.asarray(x if at is None else x[at]))
        if at is not None:
            x = x[at]
        return _logits(params, x, round_to), after


def reference_logits(cfg, params, tokens, round_to=None, **mutate):
    """tokens [S] int -> logits [S, V] float32."""
    return forward(cfg, params, tokens, round_to=round_to, **mutate)[0]


def layer_checks(cfg, params, seed: int, n_prompt: int = CHECK_PROMPT,
                 block_tokens: int = CHECK_BLOCK,
                 pass_prompt: int = PASS_PROMPT,
                 pass_bucket: int = PASS_BUCKET, n_decode: int = CHECK_DECODE,
                 page_size: int = 128, mutate: Optional[Dict[str, Any]] = None,
                 round_to=None, tamper=None) -> Dict[str, Dict[str, float]]:
    """Pieces of the system ALONE against this file's, in the weights' own
    dtype on the system's side: ``{check: {"rel_err", "tol"}}``.

    ``block_padded_prompt``: the last layer's block (four norms, attention,
    the MLP) over a seeded ``[1, block_tokens, d]`` activation of which
    ``n_prompt`` positions are read, the system's against this file's over
    those.

    ``pass_<r>_x``, r = 1 .. passes: x after pass ``r`` (its final norm
    done) at the last of ``n_decode`` teacher-forced decode steps behind a
    prompt of ``pass_prompt`` tokens padded to ``pass_bucket``, through the
    system's paged path, read whole (:func:`rms_rel_err`).  The prompt goes
    through the system's prefill ONCE, at the model's own passes; the decode
    steps are then run by the system's model of ``r`` passes whose head is
    the identity (its "logits" are its x after pass ``r``) on the FIRST ``r x
    layers`` layers of that cache: pass ``r'``'s rows depend on nothing
    after pass ``r'``, so they are that model's own cache if, and only if,
    pass ``r'`` layer ``l`` lies at ``r' x layers + l``.  A system that runs
    one pass fewer, leaves out the norm between two passes, or reads pass
    ``r'``'s rows in pass ``r`` fails the check of the pass where it first
    shows, by name.

    ``mutate`` (a test's) changes this file's side (:func:`spec`);
    ``round_to`` rounds this file's weights and activations through a
    narrower dtype; ``tamper`` (``cache -> cache``) is applied to the
    system's pool between the prefill and the decode steps, as a system
    that misplaces a pass's rows would leave it.  Each must push a check
    past its limit."""
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models import transformer as system

    _check(cfg)
    mutate = mutate or {}
    s = spec(cfg, **mutate)
    room = TOY_ROOM if cfg.hidden_size < TOY_HIDDEN else 1.0
    dtype = params["embed"].dtype
    rng = np.random.default_rng(seed)
    out: Dict[str, Dict[str, float]] = {}

    # -- one block over a padded prompt (x of order one: it has been normed
    # by the pass before, or is an embedding row of the same order of
    # magnitude relative to the branches' own norms)
    i = cfg.num_layers - 1
    h = jnp.asarray(rng.standard_normal(
        (1, block_tokens, cfg.hidden_size)).astype(np.float32)).astype(dtype)
    positions = jnp.arange(block_tokens, dtype=jnp.int32)

    def system_block(leaves, h):
        lp = {k: v[i] for k, v in leaves.items()}
        return system._block(
            cfg, lp, h, positions[None], jax.random.PRNGKey(0),
            system._attend_full(cfg, positions[None]))[0][0]

    got = jax.jit(system_block)(params["layers"], h)[:n_prompt]
    with jax.default_matmul_precision("highest"):
        h_ref = (h[0, :n_prompt].astype(round_to) if round_to is not None
                 else h[0, :n_prompt]).astype(F32)
        want = jax.jit(lambda lp, x: block(
            s, _f32(lp), x, positions[:n_prompt]))(
            _layer(params, i, round_to), h_ref)
    out["block_padded_prompt"] = {"rel_err": rel_err(got, want),
                                  "tol": room * BLOCK_REL_TOL}

    # -- x after each pass through the paged prefill and the decode steps
    R, L = cfg.loop_passes, cfg.num_layers
    model = CausalLM(cfg)
    total = pass_prompt + n_decode
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, total))
                       .astype(np.int32))
    n_pages = -(-max(total, pass_bucket) // page_size)
    cache = model.init_paged_cache(1 + n_pages, page_size, dtype=dtype)
    table = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)[None]
    prompt = jnp.zeros((1, pass_bucket), jnp.int32).at[:, :pass_prompt].set(
        toks[:, :pass_prompt])
    real = (jnp.arange(pass_bucket) < pass_prompt)[None]
    _, cache = jax.jit(lambda p, t, c: model.apply_paged(
        p, t, c, table, jnp.zeros((1,), jnp.int32), real,
        logits_at=jnp.full((1,), pass_prompt - 1, jnp.int32)))(
        params, prompt, cache)
    if tamper is not None:
        cache = tamper(cache)
    _, want_x = forward(cfg, params, toks[0], round_to=round_to,
                        rows=(total - 1,), **mutate)
    # the identity in place of the head: the model's logits are its x
    bare = {**params, "lm_head": jnp.eye(cfg.hidden_size, dtype=dtype)}
    for r in range(1, R + 1):
        part = CausalLM(dataclasses.replace(cfg, loop_passes=r))
        step = jax.jit(lambda p, t, c, start, m=part: m.apply_paged(
            p, t, c, table, start, jnp.ones((1, 1), bool)))
        own = {k: v[:r * L] for k, v in cache.items()}
        for j in range(n_decode):
            x, own = step(bare, toks[:, pass_prompt + j:pass_prompt + j + 1],
                          own, jnp.full((1,), pass_prompt + j, jnp.int32))
        # a reference mutated to fewer passes has no such pass to compare
        want = want_x[min(r, len(want_x)) - 1]
        out[f"pass_{r}_x"] = {"rel_err": rms_rel_err(x[0, 0], want[0]),
                              "tol": room * PASS_X_REL_TOL}
    return out
