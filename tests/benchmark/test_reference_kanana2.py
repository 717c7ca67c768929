"""``benchmark/lib/reference_kanana2.py``: its equations on hand-worked
cases, the system against it at a small size on seeded weights (the uncached
forward, paged prefill + decode through the latent pool, the single layers),
the eight shares of the routed experts plus the shared expert once against
the uncut layer, and the mutations each of which has to fall outside a
limit."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_kanana2 as ref
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as system

F32 = jnp.float32


def tiny(**over):
    """Kanana-2's block at a size the CPU runs in a second: a dense layer
    and three expert layers, 4 heads of 16 + 8 wide keys and 16-wide values
    from a 32-wide latent, 16 experts of which 4 are held, 3 a token, 2
    shared.  Weights wide enough (0.1) that the norm, the rotary row, the
    bias and the scale all matter."""
    kw = dict(num_layers=4, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_heads=4, head_dim=24,
              v_head_dim=16, rotary_dim=8, kv_lora_rank=32, num_experts=16,
              moe_experts_held=4, moe_top_k=3, vocab_size=256,
              max_seq_len=512, dtype=F32, initializer_range=0.1)
    kw.update(over)
    return get_config("kanana-2-30b-a3b", **kw)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, init_params(cfg, jax.random.PRNGKey(32))


# ---- the equations, by hand -------------------------------------------------

def test_rotary_turns_adjacent_pairs():
    x = jnp.ones((1, 1, 4), F32)
    got = np.asarray(ref.rotary(x, jnp.asarray([3]), 1e6))[0, 0]
    a0, a1 = 3.0, 3.0 * 1e6 ** -0.5        # pairs (0, 1) and (2, 3)
    want = [math.cos(a0) - math.sin(a0), math.cos(a0) + math.sin(a0),
            math.cos(a1) - math.sin(a1), math.cos(a1) + math.sin(a1)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the system's rotation of q_pe and the shared row is the same one
    q = jnp.asarray(np.random.default_rng(0).standard_normal((1, 5, 2, 8)),
                    F32)
    pos = jnp.arange(5)[None]
    mine, _ = system._rope(q, q[:, :, :1], pos, 1e6, 8, interleaved=True)
    np.testing.assert_allclose(mine[0], ref.rotary(q[0], pos[0], 1e6),
                               atol=1e-6)


def test_one_token_sees_its_own_value_through_the_up_projection():
    # one token, one head, rank 2, no rotary part to speak of (position 0):
    # the lone key takes the whole row, so out = v = c_normed W_UV
    s = {"heads": 1, "hd": 4, "vd": 2, "rank": 2, "rope": 2, "theta": 1e6,
         "score_dim": 4, "kv_a_norm": True, "rope_on_k": True, "eps": 0.0}
    lp = {"wq": jnp.ones((2, 4), F32),
          "wkv_a": jnp.asarray([[3.0, 0, 1, 1], [0, 4.0, 1, 1]], F32),
          "kv_a_norm_scale": jnp.asarray([1.0, 2.0], F32),
          # [k_nope (2) ; v (2)] of the one head
          "wkv_b": jnp.asarray([[1.0, 0, 1, 0], [0, 1.0, 0, 1]], F32),
          "wo": jnp.eye(2, dtype=F32)}
    h = jnp.asarray([[1.0, 1.0]], F32)
    c = np.asarray([3.0, 4.0]) / math.sqrt(12.5) * np.asarray([1.0, 2.0])
    got = ref.attention(s, lp, h, jnp.arange(1))
    np.testing.assert_allclose(got, c[None], rtol=1e-6)
    got = ref.attention(dict(s, kv_a_norm=False), lp, h, jnp.arange(1))
    np.testing.assert_allclose(got, [[3.0, 4.0]], rtol=1e-6)


def test_gates_are_renormalised_then_scaled_and_the_bias_only_chooses():
    p = np.asarray([0.6, 0.5, 0.4, 0.1])
    lp = {"router": jnp.asarray(np.log(p / (1 - p))[None], F32),
          "router_bias": jnp.asarray([0.0, 0.0, 0.3, 0.0], F32)}
    s = {"top_k": 2, "bias_in_gate": False, "routed_scale": 2.448}
    got = np.asarray(ref.expert_weights(s, lp, jnp.ones((1, 1), F32)))[0]
    np.testing.assert_allclose(got, [2.448 * 0.6, 0, 2.448 * 0.4, 0],
                               rtol=1e-5)
    got = np.asarray(ref.expert_weights(dict(s, bias_in_gate=True), lp,
                                        jnp.ones((1, 1), F32)))[0]
    np.testing.assert_allclose(got, [2.448 * 0.6 / 1.3, 0,
                                     2.448 * 0.7 / 1.3, 0], rtol=1e-5)


def test_the_shared_expert_is_every_tokens_beside_the_held_sum(model):
    cfg, params = model
    lp = ref._layer(params, "full_moe", 1)
    h = jnp.asarray(np.random.default_rng(1).standard_normal((6, 64)), F32)
    with jax.default_matmul_precision("highest"):
        both = ref.expert_layer(ref.spec(cfg), lp, h)
        routed = ref.expert_layer(ref.spec(cfg, shared=False), lp, h)
        shared = ref._swiglu(h, lp["shared_w_gate"], lp["shared_w_up"],
                             lp["shared_w_down"])
    np.testing.assert_allclose(both, routed + shared, atol=1e-5)
    assert lp["shared_w_gate"].shape == (64, 2 * 32)


# ---- the system against it --------------------------------------------------

def test_forward_matches_the_reference(model):
    cfg, params = model
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (90,)),
                       jnp.int32)
    want = ref.reference_logits(cfg, params, toks)
    got = system.forward(cfg, params, toks[None])[0]
    assert ref.rel_err(got, want) < 1e-5


@pytest.mark.parametrize("page", [16, 8])
def test_paged_prefill_and_decode_through_the_latent_pool(model, page):
    """Whole-prompt prefill (the expanded path, within the prompt), then 30
    decode steps through the latent leaf (the absorbed path) over several
    pages' edges, against the reference's full forward."""
    cfg, params = model
    lm = CausalLM(cfg)
    n_prompt, n_decode = 70, 30
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, 256, (1, n_prompt + n_decode)), jnp.int32)
    want = ref.reference_logits(cfg, params, toks[0])
    n_pages = -(-(n_prompt + n_decode) // page)
    cache = lm.init_paged_cache(1 + n_pages, page, dtype=F32)
    assert list(cache) == ["latent"]
    table = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)[None]
    step = jax.jit(lm.apply_paged)
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


def test_a_long_prompt_walks_the_causal_half_of_its_expanded_keys(
        model, monkeypatch):
    cfg, params = model
    g = system.layer_groups(cfg)["full_moe"][0]
    lp = {k: v[0] for k, v in params["layers"]["full_moe"].items()}
    h = jnp.asarray(np.random.default_rng(6).standard_normal((1, 64, 64)),
                    F32)
    pos = jnp.arange(64)[None]
    q, latent = system._qkv_latent(g, lp, h, pos)
    k, v = system._latent_expand(g, latent, lp["wkv_b"])
    assert k.shape == (1, 64, 4, 24) and v.shape == (1, 64, 4, 16)
    # every head's last 8 key dims are the one shared row
    assert (np.asarray(k[..., 16:]) == np.asarray(latent[:, :, None, 32:])
            ).all()
    want = system._attention(g, q, k, v, pos, "xla", custom_positions=True)
    monkeypatch.setattr(system, "CAUSAL_BLOCK_CHUNK", 16)
    got = system._attention_causal_block(g, q, k, v, pos)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_layer_checks_hold_as_shipped(model):
    cfg, params = model
    checks = ref.layer_checks(cfg, params, seed=4, n_tokens=64, page_size=8)
    assert set(checks) == {"latent_attention_prompt",
                           "latent_attention_decode", "expert_layer"}
    for name, c in checks.items():
        assert c["rel_err"] < 1e-5 < c["tol"], name


MUTATIONS = {
    "no-kv_a-norm": {"kv_a_norm": False},
    "rotary-left-off-k_pe": {"rope_on_k": False},
    "scale-from-the-unrotated-width": {"score_dim": 16},
    "routed-scale-1": {"routed_scale": 1.0},
    "shared-expert-left-out": {"shared": False},
    "bias-in-the-gate": {"bias_in_gate": True},
    "one-expert-fewer": {"top_k": 2},
}


@pytest.mark.parametrize("name", list(MUTATIONS))
def test_each_mutation_falls_outside_a_limit(model, name):
    cfg, params = model
    checks = ref.layer_checks(cfg, params, seed=4, n_tokens=64, page_size=8,
                              mutate=MUTATIONS[name])
    assert any(c["rel_err"] > c["tol"] for c in checks.values()), checks


def test_the_next_precision_down_falls_outside_a_limit(model):
    cfg, params = model
    checks = ref.layer_checks(cfg, params, seed=4, n_tokens=64, page_size=8,
                              round_to=jnp.float8_e4m3fn)
    assert any(c["rel_err"] > c["tol"] for c in checks.values()), checks


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """32 routed experts over 8 chips: every share routes over all 32 and
    computes its own four experts' part plus the shared expert, which every
    chip computes alike.  The eight routed parts and the shared expert
    counted ONCE add up to what the reference gives for the uncut layer
    (held = all 32), and a masked token's routed part is zero in every
    share."""
    cfg = tiny(num_experts=32, moe_experts_held=None, moe_top_k=5)
    params = init_params(cfg, jax.random.PRNGKey(8))
    layer = 2
    lp_all = {k: v[layer] for k, v in params["layers"]["full_moe"].items()}
    h = jnp.asarray(np.random.default_rng(9).standard_normal((1, 48, 64)),
                    F32)
    live = jnp.arange(48) < 40
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(ref.spec(cfg, held=(0, 32)), lp_all, h[0])
    g = system.layer_groups(cfg)["full_moe"][0]
    shared = system._dense_mlp(g, lp_all, h, prefix="shared_")[0]
    total = jnp.zeros_like(h[0])
    for share in range(8):
        first = share * 4
        held = dataclasses.replace(g, moe_experts_held=4,
                                   moe_expert_first=first)
        lp = {k: (v[first:first + 4] if k in system._EXPERT_LEAVES else v)
              for k, v in lp_all.items()}
        part, _, counts = system._mlp(held, lp, h, jax.random.PRNGKey(0),
                                      True, token_mask=live[None])
        assert counts.shape == (4,)
        routed = part[0] - shared
        assert float(jnp.abs(routed[40:]).max()) < 1e-6
        total = total + routed
    assert ref.layer_rel_err((total + shared)[:40], want[:40]) < 1e-5
    assert float(jnp.abs(want).max()) > 0
