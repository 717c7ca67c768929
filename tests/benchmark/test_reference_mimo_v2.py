"""``benchmark/lib/reference_mimo_v2.py``: its equations on hand-worked
cases, the system against it at a small size on seeded weights (the
uncached forward, paged prefill + decode across a ring's wrap, the single
layers), the share of the experts against the uncut layer, and the
mutations each of which has to fall outside a limit."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_mimo_v2 as ref
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as system

F32 = jnp.float32


def tiny(**over):
    """MiMo-V2.5's block at a size the CPU runs in a second: 7 layers (full
    + dense, window x4, full, window), 16 experts of which 4 are held, 3 a
    token, a window of 16.  Weights wide enough (0.1) that sink, bias and
    window all matter."""
    kw = dict(num_layers=7, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
              window_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
              window_size=16, num_experts=16, moe_experts_held=4, moe_top_k=3,
              vocab_size=256, max_seq_len=512, dtype=F32,
              initializer_range=0.1)
    kw.update(over)
    return get_config("mimo-v2.5", **kw)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, init_params(cfg, jax.random.PRNGKey(30))


# ---- the equations, by hand -------------------------------------------------

def _one_head_spec(**over):
    s = {"window": 128, "theta": {"full": 1e7, "window": 1e4}, "rotary": 0,
         "value_scale": 1.0, "sink": True, "heads": 1, "hd": 2, "vd": 2,
         "eps": 1e-5}
    s.update(over)
    return s


def test_sink_takes_probability_and_gives_no_value():
    # one token, one head: the row's only score is s = q.k / sqrt(2) and the
    # sink b: p = e^s / (e^b + e^s), output = p * v
    eye = jnp.eye(2, dtype=F32)
    lp = {"wq": eye, "wk": eye, "wv": eye, "wo": eye,
          "attn_sink": jnp.asarray([0.5], F32)}
    h = jnp.asarray([[1.0, 2.0]], F32)
    s = (1.0 + 4.0) / math.sqrt(2)
    p = math.exp(s) / (math.exp(0.5) + math.exp(s))
    got = ref.attention(_one_head_spec(), "window", lp, h, jnp.arange(1))
    np.testing.assert_allclose(got, p * np.asarray([[1.0, 2.0]]), rtol=1e-6)
    # a full layer has no sink: the lone key takes all of the row
    got = ref.attention(_one_head_spec(), "full", lp, h, jnp.arange(1))
    np.testing.assert_allclose(got, [[1.0, 2.0]], rtol=1e-6)


def test_window_edge_is_at_127_and_128():
    # 130 tokens, values one-hot by position, q = k = 0: a row spreads evenly
    # over what it may see, so the output IS the mask
    n = 130
    lp = {"wq": jnp.zeros((n, 2), F32), "wk": jnp.zeros((n, 2), F32),
          "wv": jnp.eye(n, dtype=F32), "wo": jnp.eye(n, dtype=F32)}
    s = _one_head_spec(sink=False, vd=n)
    got = np.asarray(ref.attention(s, "window", lp, jnp.eye(n, dtype=F32),
                                   jnp.arange(n)))
    assert got[129, 2] == pytest.approx(1 / 128)      # i - j = 127: seen
    assert got[129, 1] == 0 and got[129, 0] == 0      # 128, 129: not
    assert got[127, 0] == pytest.approx(1 / 128) and got[128, 0] == 0
    full = np.asarray(ref.attention(s, "full", lp, jnp.eye(n, dtype=F32),
                                    jnp.arange(n)))
    assert full[129, 0] == pytest.approx(1 / 130)


def test_partial_rotary_with_two_thetas():
    x = jnp.ones((1, 1, 6), F32)          # rotary on the leading 4 of 6
    for theta in (1e4, 1e7):
        got = np.asarray(ref.rotary(x, jnp.asarray([3]), theta, 4))[0, 0]
        # pairs (0, 2) and (1, 3), angles 3 * theta^(-i/2)
        a0, a1 = 3.0, 3.0 * theta ** -0.5
        want = [math.cos(a0) - math.sin(a0), math.cos(a1) - math.sin(a1),
                math.cos(a0) + math.sin(a0), math.cos(a1) + math.sin(a1),
                1.0, 1.0]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_bias_enters_the_choice_and_not_the_gate():
    # sigmoid scores 0.6, 0.5, 0.4, 0.1; the bias lifts expert 2 over expert
    # 1: chosen {0, 2}, gates 0.6 / 1.0 and 0.4 / 1.0 of the scores alone
    z = np.log(np.asarray([0.6, 0.5, 0.4, 0.1]) / (1 - np.asarray(
        [0.6, 0.5, 0.4, 0.1])))
    lp = {"router": jnp.asarray(z[None], F32),
          "router_bias": jnp.asarray([0.0, 0.0, 0.3, 0.0], F32)}
    s = {"score": "sigmoid", "top_k": 2, "norm_topk": True,
         "bias_in_gate": False}
    got = np.asarray(ref.expert_weights(s, lp, jnp.ones((1, 1), F32)))[0]
    np.testing.assert_allclose(got, [0.6, 0.0, 0.4, 0.0], rtol=1e-5)
    got = np.asarray(ref.expert_weights(dict(s, norm_topk=False), lp,
                                        jnp.ones((1, 1), F32)))[0]
    np.testing.assert_allclose(got, [0.6, 0.0, 0.4, 0.0], rtol=1e-5)
    lp.pop("router_bias")
    got = np.asarray(ref.expert_weights(s, lp, jnp.ones((1, 1), F32)))[0]
    np.testing.assert_allclose(got, [0.6 / 1.1, 0.5 / 1.1, 0, 0], rtol=1e-5)


# ---- the system against it --------------------------------------------------

def test_forward_matches_the_reference(model):
    cfg, params = model
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (90,)),
                       jnp.int32)
    want = ref.reference_logits(cfg, params, toks)
    got = system.forward(cfg, params, toks[None])[0]
    assert ref.rel_err(got, want) < 1e-5


@pytest.mark.parametrize("page,over", [
    (16, {}), (8, {}), (16, {"v_head_dim": 128, "num_heads": 4})],
    ids=["one-page-a-window", "two-pages-a-window", "head-major-values"])
def test_paged_prefill_and_decode_across_a_ring_wrap(page, over):
    """Whole-prompt prefill longer than the window (its window layers attend
    inside the prompt and keep its last rows), then 30 decode steps through
    both pools: over a page's edge and a ring's wrap, against the uncached
    forward and the reference."""
    cfg = tiny(**over)
    params = init_params(cfg, jax.random.PRNGKey(3))
    model = CausalLM(cfg)
    n_prompt, n_decode = 70, 30
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, 256, (1, n_prompt + n_decode)), jnp.int32)
    want = ref.reference_logits(cfg, params, toks[0])
    assert ref.rel_err(system.forward(cfg, params, toks)[0], want) < 1e-5
    n_pages = -(-(n_prompt + n_decode) // page)
    ring = system.window_ring_pages(cfg.window_size, page)
    assert n_pages > ring + 2          # the ring wraps more than once
    cache = model.init_paged_cache(1 + n_pages, page, dtype=F32)
    if over:
        assert cache["v"].shape[2:] == (cfg.kv_heads, page, 128)
    table = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)[None]
    step = jax.jit(model.apply_paged)
    s_pad = n_pages * page
    prompt = jnp.zeros((1, s_pad), jnp.int32).at[:, :n_prompt].set(
        toks[:, :n_prompt])
    logits, cache = step(params, prompt, cache, table,
                         jnp.zeros((1,), jnp.int32),
                         (jnp.arange(s_pad) < n_prompt)[None])
    assert ref.rel_err(logits[0, :n_prompt], want[:n_prompt]) < 1e-5
    for i in range(n_decode):
        logits, cache = step(params, toks[:, n_prompt + i:n_prompt + i + 1],
                             cache, table,
                             jnp.full((1,), n_prompt + i, jnp.int32),
                             jnp.ones((1, 1), bool))
        assert ref.rel_err(logits[0, 0], want[n_prompt + i]) < 1e-5, i


def test_a_long_block_takes_its_chunks_some_at_a_time(monkeypatch):
    cfg = tiny(window_size=8)
    g = system.layer_groups(cfg)["window_moe"][0]
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 64, 8, 24)), F32)
    k = jnp.asarray(rng.standard_normal((1, 64, 4, 24)), F32)
    v = jnp.asarray(rng.standard_normal((1, 64, 4, 16)), F32)
    sink = jnp.asarray(rng.standard_normal(8), F32)
    pos = jnp.arange(64)[None]
    want = system._attention(g, q, k, v, pos, "xla", custom_positions=True,
                             window=8, sink=sink)
    whole = system._attention_window_block(g, q, k, v, pos, 8, sink)
    monkeypatch.setattr(system, "WINDOW_BLOCK_CHUNKS", 2)
    mapped = system._attention_window_block(g, q, k, v, pos, 8, sink)
    np.testing.assert_allclose(whole, want, atol=2e-6)
    np.testing.assert_allclose(mapped, want, atol=2e-6)


def test_a_long_blocks_full_layers_walk_the_causal_half(monkeypatch):
    cfg = tiny()
    g = system.layer_groups(cfg)["full_moe"][0]
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((2, 64, 8, 24)), F32)
    k = jnp.asarray(rng.standard_normal((2, 64, 2, 24)), F32)
    v = jnp.asarray(rng.standard_normal((2, 64, 2, 16)), F32)
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    want = system._attention(g, q, k, v, pos, "xla", custom_positions=True)
    monkeypatch.setattr(system, "CAUSAL_BLOCK_CHUNK", 16)
    got = system._attention_causal_block(g, q, k, v, pos)
    assert got.shape == (2, 64, 8, 16)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_layer_checks_hold_as_shipped(model):
    cfg, params = model
    checks = ref.layer_checks(cfg, params, seed=4, n_tokens=64)
    assert set(checks) == {"window_attention", "full_attention",
                           "expert_layer"}
    for name, c in checks.items():
        assert c["rel_err"] < 1e-5 < c["tol"], name


MUTATIONS = {
    "no-sink": {"sink": False},
    "window-doubled": {"window": 32},
    "value-scale-1": {"value_scale": 1.0},
    "thetas-swapped": {"theta": {"full": 1e4, "window": 1e7}},
    "softmax-for-sigmoid": {"score": "softmax"},
    "bias-in-the-gate": {"bias_in_gate": True},
    "no-renormalisation": {"norm_topk": False},
    "one-expert-fewer": {"top_k": 2},
    "held-range-shifted": {"held": (1, 4)},
}


@pytest.mark.parametrize("name", list(MUTATIONS))
def test_each_mutation_falls_outside_a_limit(model, name):
    cfg, params = model
    checks = ref.layer_checks(cfg, params, seed=4, n_tokens=64,
                              mutate=MUTATIONS[name])
    assert any(c["rel_err"] > c["tol"] for c in checks.values()), checks


def test_the_next_precision_down_falls_outside_a_limit(model):
    cfg, params = model
    checks = ref.layer_checks(cfg, params, seed=4, n_tokens=64,
                              round_to=jnp.float8_e4m3fn)
    assert any(c["rel_err"] > c["tol"] for c in checks.values()), checks


def test_the_shares_add_up_to_the_uncut_layer():
    """32 experts over 16 chips: every share routes over all 32 and computes
    its own two experts' part; the 16 parts add up to what the reference
    gives for the whole layer (held = all 32), and a masked token's row is
    zero in every part."""
    cfg = tiny(num_experts=32, moe_experts_held=None, moe_top_k=5)
    params = init_params(cfg, jax.random.PRNGKey(8))
    leaves = params["layers"]["window_moe"]
    layer = 2
    h = jnp.asarray(np.random.default_rng(9).standard_normal((1, 48, 64)),
                    F32)
    live = jnp.arange(48) < 40
    lp_all = {k: v[layer] for k, v in leaves.items()}
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(ref.spec(cfg, held=(0, 32)), lp_all, h[0])
    total = jnp.zeros_like(h[0])
    for share in range(16):
        first = share * 2
        shared = dataclasses.replace(
            system.layer_groups(cfg)["window_moe"][0], moe_experts_held=2,
            moe_expert_first=first)
        lp = {k: (v[first:first + 2] if k in system._EXPERT_LEAVES else v)
              for k, v in lp_all.items()}
        part, _, counts = system._mlp(shared, lp, h, jax.random.PRNGKey(0),
                                      True, token_mask=live[None])
        assert counts.shape == (2,)
        assert not np.asarray(part[0, 40:]).any()
        total = total + part[0]
    assert ref.rel_err(total[:40], want[:40]) < 1e-5
    assert float(jnp.abs(want).max()) > 0
