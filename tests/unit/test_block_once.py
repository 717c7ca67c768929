"""One block body under the three forwards (ISSUE 28).

``forward``, ``forward_cached`` (prefill, then one token at a time) and
``forward_paged`` (one prefill bucket, then decode ticks through a page
table) run the same ``_block`` and differ only in what attention reads.  At a
tiny float32 size the three give the same logits for the same tokens, for
every residual wiring a causal model has, and each agrees with the
benchmark's plain reference where that covers the block (OPT, GPT-NeoX).
The post-LN encoder runs ``forward`` alone, against a plain post-LN block
written here, with biases that are not zero (a HuggingFace model is born
with zero biases, so the parity tests against it cannot see where a bias is
added).

Not repeated here: ``forward`` against HuggingFace for every family
(``test_module_inject.py``), ``forward`` against the reference for OPT and
GPT-NeoX (``tests/benchmark/test_reference.py``), and all three paths of
OLMoE against its reference (``tests/benchmark/test_reference_olmoe.py``).
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import reference
from deepspeed_tpu.models import (TransformerConfig, forward, forward_cached,
                                  forward_paged, init_cache, init_paged_cache,
                                  init_params)

N_PROMPT, N_DECODE, PAGE = 16, 8, 8
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, max_seq_len=64, norm="layernorm",
            attn_bias=True, mlp_bias=True, remat=False, dtype=jnp.float32)
TOPOLOGIES = {
    # pre-LN, learned positions, biases, tied head
    "opt": dict(activation="relu", position="learned", tie_embeddings=True),
    # parallel residual, two norms, rotary on part of the head
    "pythia": dict(activation="gelu_exact", parallel_residual=True,
                   rotary_dim=4),
    # parallel residual, the one shared norm, interleaved rotary, head bias
    "gptj": dict(activation="gelu", parallel_residual=True,
                 shared_layernorm=True, rotary_dim=8, rope_interleaved=True,
                 attn_bias=False, lm_head_bias=True),
}
BERT = dict(activation="gelu_exact", position="learned", causal=False,
            post_layernorm=True, embed_layernorm=True, type_vocab_size=2,
            final_norm=False, tie_embeddings=True, norm_eps=1e-12)


def _model(overrides):
    cfg = TransformerConfig(**{**TINY, **overrides})
    params = init_params(cfg, jax.random.PRNGKey(3))
    # biases and norm offsets start at 0 and 1: randomise so a dropped bias
    # or a swapped norm would show
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    return cfg, jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module", params=list(TOPOLOGIES))
def model(request):
    cfg, params = _model(TOPOLOGIES[request.param])
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, N_PROMPT + N_DECODE)).astype(np.int32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(cfg, params, tokens, attn_impl="xla"))
    return cfg, params, tokens, want


def _cached(cfg, params, tokens):
    B, S = tokens.shape
    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    step = jax.jit(lambda *a: forward_cached(cfg, params, *a))
    logits, cache = step(tokens[:, :N_PROMPT], cache, pos[:, :N_PROMPT],
                         jnp.ones((B, N_PROMPT), bool))
    out = [logits]
    for i in range(N_PROMPT, S):
        logits, cache = step(tokens[:, i:i + 1], cache, pos[:, i:i + 1],
                             jnp.ones((B, 1), bool))
        out.append(logits)
    return np.concatenate([np.asarray(o) for o in out], axis=1)


def _paged(cfg, params, tokens):
    """Each prompt through a one-row prefill bucket twice its length, then
    decode ticks over three slots of which the middle one idles."""
    B, S = tokens.shape
    n_pg = -(-S // PAGE)
    cache = init_paged_cache(cfg, 1 + 3 * n_pg, PAGE, dtype=jnp.float32)
    table = 1 + jnp.arange(3 * n_pg, dtype=jnp.int32).reshape(3, n_pg)
    rows = jnp.asarray([0, 2])
    step = jax.jit(lambda *a: forward_paged(cfg, params, *a))
    bucket = jnp.zeros((1, 2 * N_PROMPT), jnp.int32)
    mask = (jnp.arange(2 * N_PROMPT) < N_PROMPT)[None]
    fills = []
    for b, row in enumerate((0, 2)):
        logits, cache = step(bucket.at[:, :N_PROMPT].set(tokens[b, :N_PROMPT]),
                             cache, table[row:row + 1],
                             jnp.zeros((1,), jnp.int32), mask)
        fills.append(logits[:, :N_PROMPT])
    out = [jnp.concatenate(fills)]
    live = jnp.asarray([True, False, True])[:, None]
    for i in range(N_PROMPT, S):
        tok = jnp.zeros((3, 1), jnp.int32).at[rows, 0].set(tokens[:, i])
        logits, cache = step(tok, cache, table, jnp.full((3,), i, jnp.int32),
                             live)
        out.append(logits[rows])
    return np.concatenate([np.asarray(o) for o in out], axis=1)


PATHS = {"contiguous cache": _cached, "paged prefill + decode": _paged}


@pytest.mark.parametrize("path", list(PATHS))
def test_a_cached_forward_gives_the_training_forwards_logits(model, path):
    cfg, params, tokens, want = model
    with jax.default_matmul_precision("highest"):
        got = PATHS[path](cfg, params, tokens)
    assert reference.rel_err(got, want) < 2e-5


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("model", ["opt", "pythia"], indirect=True)
def test_a_cached_forward_matches_the_plain_reference(model, path):
    cfg, params, tokens, _ = model      # the blocks reference.py covers
    with jax.default_matmul_precision("highest"):
        got = PATHS[path](cfg, params, tokens)
    for b in range(tokens.shape[0]):
        ref = reference.reference_logits(cfg, params, tokens[b])
        assert reference.rel_err(got[b], ref) < 2e-5


def _plain_post_ln_encoder(cfg, p, tokens, segments):
    """BERT as its paper writes it, float64 on the host: the embedding sum
    normed, then ``x = LN(x + attn(x)); x = LN(x + mlp(x))`` with
    bidirectional attention, then the tied head."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)

    def ln(x, scale, bias):
        mu, var = x.mean(-1, keepdims=True), x.var(-1, keepdims=True)
        return (x - mu) / np.sqrt(var + cfg.norm_eps) * scale + bias

    S, H, hd = tokens.shape[0], cfg.num_heads, cfg.dims_per_head
    x = (p["embed"][tokens] + p["pos_embed"][np.arange(S)]
         + p["type_embed"][segments])
    x = ln(x, p["embed_norm_scale"], p["embed_norm_bias"])
    for i in range(cfg.num_layers):
        lp = {k: v[i] for k, v in p["layers"].items()}
        q, k, v = ((x @ lp["w" + n] + lp["b" + n]).reshape(S, H, hd)
                   for n in "qkv")
        s = np.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        w = np.exp(s - s.max(-1, keepdims=True))
        a = np.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), v)
        x = ln(x + a.reshape(S, H * hd) @ lp["wo"] + lp["bo"],
               lp["attn_norm_scale"], lp["attn_norm_bias"])
        m = x @ lp["w_in"] + lp["b_in"]
        m = 0.5 * m * (1.0 + np.vectorize(math.erf)(m / math.sqrt(2.0)))
        x = ln(x + m @ lp["w_down"] + lp["b_down"],
               lp["mlp_norm_scale"], lp["mlp_norm_bias"])
    return x @ p["embed"].T


def test_the_post_ln_encoder_runs_the_same_block():
    cfg, params = _model(BERT)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    segments = rng.integers(0, 2, (2, 12)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(cfg, params, jnp.asarray(tokens),
                                 attn_impl="xla",
                                 token_type_ids=jnp.asarray(segments)))
    for b in range(2):
        ref = _plain_post_ln_encoder(cfg, params, tokens[b], segments[b])
        assert reference.rel_err(got[b], ref) < 2e-5
