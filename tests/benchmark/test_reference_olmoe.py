"""reference_olmoe.py against models/transformer.py on seeded float32
weights at a tiny OLMoE shape (4 layers, 8 experts top-3, QK-norm, gates
not renormalised): the training forward, generate()'s contiguous cache, the
paged prefill and decode with a padded prompt and idle slots; the three
mutations a tolerance has to catch; masked tokens in the dropless dispatch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_olmoe as ref
from benchmark.lib import system

TOL = 2e-5      # float32 against float32, as test_reference.py holds the dense blocks
N_PROMPT, N_DECODE, PAGE = 21, 3, 8
MUTATIONS = {
    "top-(k-1)": dict(moe_top_k=2),
    "renormalised gates": dict(moe_norm_topk_prob=True),
    "no QK-norm": dict(qk_norm=False),
}


@pytest.fixture(scope="module")
def cfg():
    tiny = system.transformer_config(
        system.load_json("configs", "olmoe-1b-7b-d12.json"), rehearse=True)
    return dataclasses.replace(tiny, num_layers=4, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    from deepspeed_tpu.models import init_params

    tree = init_params(cfg, jax.random.PRNGKey(3))
    # the norm scales start at 1: randomise so a dropped or swapped scale shows
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tokens(cfg):
    return jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, N_PROMPT + N_DECODE)).astype(np.int32))


@pytest.fixture(scope="module")
def want(cfg, params, tokens):
    return [np.asarray(ref.reference_logits(cfg, params, tokens[b]))
            for b in range(2)]


def _paged(cfg, params, tokens, pad_fill=0):
    """Rows 0 and 2 of a 3-slot batch hold the two prompts (row 1 idles),
    padded to whole pages with ``pad_fill``; prefill, then N_DECODE decode
    steps.  -> (logits [2, N_PROMPT + N_DECODE, V], counts of each call)."""
    from deepspeed_tpu.models import forward_paged, init_paged_cache

    n_pg = -(-(N_PROMPT + N_DECODE) // PAGE)
    s_pad = n_pg * PAGE
    cache = init_paged_cache(cfg, 1 + 3 * n_pg, PAGE, dtype=jnp.float32)
    table = 1 + jnp.arange(3 * n_pg, dtype=jnp.int32).reshape(3, n_pg)
    live = jnp.asarray([True, False, True])
    step = jax.jit(lambda *a: forward_paged(cfg, params, *a,
                                            expert_counts=True))
    prompt = jnp.full((3, s_pad), pad_fill, jnp.int32)
    prompt = prompt.at[jnp.asarray([0, 2]), :N_PROMPT].set(tokens[:, :N_PROMPT])
    mask = (jnp.arange(s_pad) < N_PROMPT)[None] & live[:, None]
    with jax.default_matmul_precision("highest"):
        logits, cache, counts = step(prompt, cache, table,
                                     jnp.zeros((3,), jnp.int32), mask)
        out, all_counts = [logits[jnp.asarray([0, 2]), :N_PROMPT]], [counts]
        for i in range(N_DECODE):
            tok = jnp.full((3, 1), pad_fill, jnp.int32).at[
                jnp.asarray([0, 2]), 0].set(tokens[:, N_PROMPT + i])
            logits, cache, counts = step(
                tok, cache, table, jnp.full((3,), N_PROMPT + i, jnp.int32),
                live[:, None])
            out.append(logits[jnp.asarray([0, 2])])
            all_counts.append(counts)
    return np.concatenate([np.asarray(o) for o in out], axis=1), all_counts


def _forward(cfg, params, tokens):
    from deepspeed_tpu.models import forward

    with jax.default_matmul_precision("highest"):
        return np.asarray(forward(cfg, params, tokens, attn_impl="xla"))


def _cached(cfg, params, tokens):
    """generate()'s path: prefill the prompt into the contiguous cache, then
    one token at a time."""
    from deepspeed_tpu.models import forward_cached, init_cache

    B, S = tokens.shape
    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    step = jax.jit(lambda *a: forward_cached(cfg, params, *a))
    with jax.default_matmul_precision("highest"):
        logits, cache = step(tokens[:, :N_PROMPT], cache, pos[:, :N_PROMPT],
                             jnp.ones((B, N_PROMPT), bool))
        out = [logits]
        for i in range(N_PROMPT, S):
            logits, cache = step(tokens[:, i:i + 1], cache, pos[:, i:i + 1],
                                 jnp.ones((B, 1), bool))
            out.append(logits)
    return np.concatenate([np.asarray(o) for o in out], axis=1)


PATHS = {"training forward": _forward, "contiguous cache": _cached,
         "paged prefill + decode": lambda *a: _paged(*a)[0]}


@pytest.mark.parametrize("path", list(PATHS))
def test_reference_matches_system(cfg, params, tokens, want, path):
    got = PATHS[path](cfg, params, tokens)
    for b in range(2):
        assert ref.rel_err(got[b], want[b]) < TOL


@pytest.mark.parametrize("mutation", list(MUTATIONS))
@pytest.mark.parametrize("path", ["training forward", "paged prefill + decode"])
def test_mutation_fails_the_check(cfg, params, tokens, want, path, mutation):
    """The system with one rule of the architecture changed is far outside
    the tolerance that the unchanged system is inside."""
    mutant = dataclasses.replace(cfg, **MUTATIONS[mutation])
    got = PATHS[path](mutant, params, tokens)
    assert min(ref.rel_err(got[b], want[b]) for b in range(2)) > 100 * TOL


def test_reference_refuses_other_blocks():
    from deepspeed_tpu.models import get_config

    for name in ("tiny", "tiny-moe"):      # dense; renormalised, dropping
        with pytest.raises(NotImplementedError):
            ref.reference_logits(get_config(name), {},
                                 jnp.zeros((4,), jnp.int32))


def test_masked_tokens_take_no_row_and_change_nothing(cfg, params, tokens):
    """A prompt's padding and an idle slot are in no expert's group, whatever
    they hold: the counts are the live tokens' alone, and the live logits do
    not depend on what the masked positions hold."""
    got, counts = _paged(cfg, params, tokens, pad_fill=0)
    again, counts2 = _paged(cfg, params, tokens, pad_fill=77)
    np.testing.assert_array_equal(got, again)
    k, layers = cfg.moe_top_k, cfg.num_layers
    for call, live_tokens in zip(counts, [2 * N_PROMPT] + [2] * N_DECODE):
        assert call.shape == (layers, cfg.num_experts)
        # moe_rows == moe_live_rows, layer by layer
        np.testing.assert_array_equal(np.asarray(call).sum(-1),
                                      live_tokens * k)
    for a, b in zip(counts, counts2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unmasked_dispatch_counts_every_token(cfg, params):
    """Without a mask every token is real: the one formulation."""
    from deepspeed_tpu.models.transformer import _mlp

    lp = {k: v[0] for k, v in params["layers"].items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.hidden_size))
    mask = jnp.arange(9)[None] < jnp.asarray([9, 4])[:, None]
    full, _, n_full = _mlp(cfg, lp, h, None, True)
    part, _, n_part = _mlp(cfg, lp, h, None, True, token_mask=mask)
    assert int(n_full.sum()) == 18 * cfg.moe_top_k
    assert int(n_part.sum()) == 13 * cfg.moe_top_k
    np.testing.assert_allclose(np.asarray(part)[mask], np.asarray(full)[mask],
                               rtol=1e-6, atol=1e-7)
    assert not np.asarray(part)[~np.asarray(mask)].any()


def test_layer_checks_hold_and_catch_the_mutations(cfg, params):
    """``layer_checks`` as a cell runs it (bfloat16 system, float32
    reference), and its two limits against the three mutations: the expert
    layer's catches the routing ones, q and k's the missing norm."""
    bf16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    run = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    checks = ref.layer_checks(run, bf16, seed=2 ** 31 + 11, n_tokens=64)
    assert set(checks) == {"expert_layer", "qk_norm"}
    assert all(c["rel_err"] <= c["tol"] for c in checks.values()), checks
    limits = {"expert_layer": ref.EXPERT_LAYER_REL_TOL,
              "qk_norm": ref.QK_REL_TOL}
    for mutation, check in [("top-(k-1)", "expert_layer"),
                            ("renormalised gates", "expert_layer"),
                            ("no QK-norm", "qk_norm")]:
        got = ref.layer_checks(
            run, bf16, seed=2 ** 31 + 11, n_tokens=64,
            system_cfg=dataclasses.replace(run, **MUTATIONS[mutation]))
        assert got[check]["rel_err"] > 2 * limits[check], (mutation, got)
