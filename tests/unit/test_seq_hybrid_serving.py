"""A model whose layers are EITHER a state-space mixer OR attention, an expert
layer behind every one (Granite 4.0-H, ISSUE 47): the third kind of layer in
``layer_pattern``, the one rule for which sublayers a layer has, the cache
whose paged leaves and state leaves cover different layers, the four fixed
multipliers, the held share of the experts, and the serving engine over all
of it, at the benchmark's rehearsal size against the plain reference
(``benchmark/lib/reference_granite4h.py``)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_granite4h as R
from benchmark.lib import system
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models import mixers
from deepspeed_tpu.models.mixers import common as MX
from deepspeed_tpu.models.mixers import ssm as SSM

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PAGE, CHUNK = 16, 8


def _rehearse_cfg(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-small-ep2-d10.json")) as f:
        cfg = system.transformer_config(json.load(f), rehearse=True)
    return dataclasses.replace(cfg, **over)


@pytest.fixture(scope="module")
def tiny():
    cfg = _rehearse_cfg(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(7))


def _tokens(cfg, n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n,)).astype(np.int32))


# ------------------------------------------------- the plan and the leaves

def test_the_pattern_takes_a_third_kind_and_groups_by_it(tiny):
    cfg, params = tiny
    assert cfg.ssm_chunk == CHUNK and cfg.num_layers == 10
    plan = T.layer_plan(cfg)
    assert [kind for _, _, kind, _ in plan] == ["ssm"] * 5 + ["full"] + [
        "ssm"] * 4
    assert [(g, i) for g, i, _, _ in plan] == R.plan(cfg)
    groups = T.layer_groups(cfg)
    assert list(groups) == ["ssm_moe", "full_moe"]
    assert [n for _, n in groups.values()] == [9, 1]
    # one rule: (attention, mixer, mlp) of each group, and of a parallel
    # block
    assert T.sublayers(groups["ssm_moe"][0]) == (False, True, True)
    assert T.sublayers(groups["full_moe"][0]) == (True, False, True)
    assert T.sublayers(get_config("falcon-h1-34b")) == (True, True, True)
    assert T.sublayers(get_config("tiny")) == (True, False, True)
    # the parameters follow it: no attention leaf in a mamba layer, no mixer
    # leaf in an attention layer, an expert layer behind both
    ssm, full = params["layers"]["ssm_moe"], params["layers"]["full_moe"]
    assert not {"wq", "wk", "wv", "wo"} & set(ssm) and "ssm_in" in ssm
    assert not any(k.startswith("ssm_") for k in full) and "wq" in full
    for lp, n in ((ssm, 9), (full, 1)):
        assert lp["router"].shape == (n, 64, 12)
        assert lp["w_gate"].shape == (n, 6, 64, 24)          # 6 of 12 held
        assert lp["shared_w_gate"].shape == (n, 64, 48)      # 2 x 24 wide
    assert "lm_head" not in params and "pos_embed" not in params
    assert cfg.param_count == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert jax.tree_util.tree_structure(T.param_specs(cfg)) == \
        jax.tree_util.tree_structure(params)


def test_the_caches_leaves_cover_different_layers(tiny):
    cfg, _ = tiny
    assert T.cache_layers(cfg) == (1, 9) and T.cache_depth(cfg) == 1
    assert T.cache_kind(cfg)[0] == "state" and T.is_hybrid(cfg)
    cache = CausalLM(cfg).init_paged_cache(5, PAGE, dtype=jnp.bfloat16,
                                           slots=3)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 5, PAGE, 2, 16), "v": (1, 5, PAGE, 2, 16),
        "ssm_state": (9, 3, 4, 32, 16), "ssm_conv": (9, 3, 3, 160)}
    assert cache["ssm_state"].dtype == jnp.float32
    assert set(T.paged_cache_specs(cfg)) == set(cache)
    # Falcon-H1's parallel block: both leaves as deep as the model
    falcon = get_config("falcon-h1-34b", num_layers=5)
    assert T.cache_layers(falcon) == (5, 5)
    # the published sizes: 1 layer of K/V, 9 of state, 4,244,992 B a slot a
    # mamba layer, 4,096 B a token
    big = get_config("granite-4.0-h-small", num_layers=10)
    shapes = jax.eval_shape(lambda: T.init_paged_cache(
        big, 3, 128, dtype=jnp.bfloat16, slots=2))
    assert shapes["k"].shape == (1, 3, 128, 8, 128)
    assert shapes["ssm_state"].shape == (9, 2, 128, 64, 128)
    assert shapes["ssm_conv"].shape == (9, 2, 3, 8448)
    assert 128 * 64 * 128 * 4 + 3 * 8448 * 2 == 4_244_992
    assert SSM.ssm_in_width(big) == 16768


REFUSED = {
    "window layers": dict(layer_pattern=("ssm", "window", "full") * 4),
    "one kind run": dict(num_layers=5),
    "leading dense layers": dict(dense_layers=1),
    "latent attention": dict(kv_lora_rank=8, rotary_dim=8, position="rope"),
    "a num_experts tuple": dict(num_experts=(12,) * 10),
    "parallel_residual": dict(parallel_residual=True),
    "pipeline_stages": dict(pipeline_stages=2),
    "attn_bias": dict(attn_bias=True),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_what_is_still_not_built_is_refused_by_name(what):
    said = {"window layers": "window layers in one layer_pattern",
            "one kind run": "not of both kinds",
            "leading dense layers": "dense_layers",
            "latent attention": "latent attention",
            "a num_experts tuple": "per-layer expert counts",
            "parallel_residual": "parallel_residual",
            "pipeline_stages": "pipeline_stages",
            "attn_bias": "attn_bias under a layer_pattern"}[what]
    with pytest.raises(NotImplementedError, match=said):
        mixers.check(_rehearse_cfg(**REFUSED[what]))


def test_an_unknown_kind_and_a_mixerless_ssm_layer_are_value_errors():
    with pytest.raises(ValueError, match="full | window | ssm"):
        T.layer_plan(_rehearse_cfg(layer_pattern=("ssm", "mamba") * 5))
    with pytest.raises(ValueError, match="no state-space layers"):
        T.layer_plan(get_config("tiny", layer_pattern=("ssm", "full")))


# ---------------------------------------------------- against the reference

@pytest.mark.parametrize("seed", [0, 1])
def test_full_forward_is_the_references(tiny, seed):
    cfg, params = tiny
    toks = _tokens(cfg, 37, seed)
    got = T.forward(cfg, params, toks[None])[0]
    assert R.rel_err(got, R.reference_logits(cfg, params, toks)) < 1e-5


MULTIPLIERS = {"embedding_multiplier": ("embed_mult", "embed_multiplier"),
               "attention_multiplier": ("attn_scale", "attn_softmax_scale"),
               "residual_multiplier": ("residual_mult",
                                       "residual_multiplier"),
               "logits_scaling": ("logits_mult", "lm_head_multiplier")}


@pytest.mark.parametrize("which", list(MULTIPLIERS))
def test_each_of_the_four_multipliers_changes_the_output(tiny, which):
    """The system under the configuration's multiplier is the reference's
    under the same, and NOT the reference's with that multiplier at what a
    model without it has: dropped from the forward, this fails."""
    cfg, params = tiny
    ref_key, field = MULTIPLIERS[which]
    plain = (1.0 / np.sqrt(cfg.dims_per_head) if field == "attn_softmax_scale"
             else 1.0)
    assert getattr(cfg, field) not in (plain, None)
    toks = _tokens(cfg, 33, 2)
    got = T.forward(cfg, params, toks[None])[0]
    assert R.rel_err(got, R.reference_logits(cfg, params, toks)) < 1e-5
    # (the toy's scores are small: its softmax scale moves the logits least)
    assert R.rel_err(got, R.reference_logits(
        cfg, params, toks, **{ref_key: plain})) > 2e-4
    # and the system's own field moves the system the same way
    dropped = dataclasses.replace(
        cfg, **{field: None if field == "attn_softmax_scale" else 1.0})
    assert R.rel_err(T.forward(dropped, params, toks[None])[0],
                     R.reference_logits(cfg, params, toks,
                                        **{ref_key: plain})) < 1e-5


# (prompt, decode steps): ends inside a page and a chunk; crosses a page (16)
# in the prompt and again in decode; a prompt of whole chunks and pages
PAGED = [(29, 6), (21, 14), (32, 3)]


@pytest.mark.parametrize("n_prompt,n_decode", PAGED)
def test_prefill_then_paged_decode_is_the_references_full_forward(
        tiny, n_prompt, n_decode):
    """Logits, not tokens: the padded prompt's prefill into slot 1 of 3, then
    teacher-forced decode steps through the page of the attention layer and
    the state rows of the nine mamba layers; then the SAME slot again for
    another sequence (its state starts from zeros, its pages are
    overwritten)."""
    cfg, params = tiny
    model = CausalLM(cfg)
    total = n_prompt + n_decode
    n_pages = -(-total // PAGE)
    s_pad = n_pages * PAGE
    cache = model.init_paged_cache(1 + n_pages, PAGE, dtype=jnp.float32,
                                   slots=3)
    table = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)[None]
    slot = jnp.ones((1,), jnp.int32)
    step = jax.jit(lambda p, t, c, start, mask: model.apply_paged(
        p, t, c, table, start, mask, state_slot=slot))
    for seed in (3, 4):         # the second sequence reuses the slot
        toks = _tokens(cfg, total, seed)
        want = R.reference_logits(cfg, params, toks)
        prompt = jnp.zeros((1, s_pad), jnp.int32).at[0, :n_prompt].set(
            toks[:n_prompt])
        logits, cache = step(params, prompt, cache, jnp.zeros((1,), jnp.int32),
                             (jnp.arange(s_pad) < n_prompt)[None])
        assert R.rel_err(logits[0, :n_prompt], want[:n_prompt]) < 1e-5
        for i in range(n_decode):
            logits, cache = step(params, toks[None, n_prompt + i:][:, :1],
                                 cache,
                                 jnp.full((1,), n_prompt + i, jnp.int32),
                                 jnp.ones((1, 1), bool))
            assert R.rel_err(logits[0, 0], want[n_prompt + i]) < 1e-5
        # the other slots' rows were never touched
        assert float(jnp.abs(cache["ssm_state"][:, (0, 2)]).max()) == 0.0


def test_the_layer_checks_hold_one_layer_of_each_kind_and_an_expert_layer(
        tiny):
    cfg, params = tiny
    checks = R.layer_checks(cfg, params, seed=5, n_prompt=21, block_tokens=32,
                            n_decode=12, page_size=PAGE)
    assert set(checks) == {
        "state_layer_block", "attention_layer_block", "expert_layer",
        "state_after_prefill", "state_after_decode", "logits_after_decode",
        "other_slots_untouched"}
    for name, c in checks.items():
        assert c["rel_err"] <= min(c["tol"], 1e-5), (name, c)


MUTATIONS = {
    "no shared expert": ({"shared": False}, "expert_layer"),
    "no convolution bias": ({"conv_bias": False}, "state_layer_block"),
    "no D skip": ({"skip_d": False}, "state_layer_block"),
    "scores over sqrt(head_dim)": ({"attn_scale": 0.25},
                                   "attention_layer_block"),
    "no residual multiplier": ({"residual_mult": 1.0}, "state_layer_block"),
    "the other chip's experts": ({"held": (6, 6)}, "expert_layer"),
}


@pytest.mark.parametrize("what", list(MUTATIONS))
def test_a_mutated_reference_is_told_from_the_system(tiny, what):
    """Each departure from the equations moves the check of its layer by a
    thousand times what float32 rounding does."""
    cfg, params = tiny
    mutate, check = MUTATIONS[what]
    checks = R.layer_checks(cfg, params, seed=5, n_prompt=21, block_tokens=32,
                            n_decode=4, page_size=PAGE, mutate=mutate)
    assert checks[check]["rel_err"] > 1e-3, checks[check]


def test_a_state_kept_in_bfloat16_reads_worse_than_one_in_float32():
    cfg = _rehearse_cfg()
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        init_params(cfg, jax.random.PRNGKey(7)))
    kw = dict(seed=5, n_prompt=21, block_tokens=32, n_decode=40,
              page_size=PAGE)
    f32 = R.layer_checks(cfg, params, **kw)["state_after_decode"]
    bf16 = R.layer_checks(cfg, params, mutate={
        "state_dtype": jnp.bfloat16}, **kw)["state_after_decode"]
    assert f32["rel_err"] <= f32["tol"]
    assert bf16["rel_err"] > f32["rel_err"]


def test_the_two_chips_shares_add_up_to_the_whole_layer(tiny):
    """The share test: the system's expert layer as each of the stage's two
    chips holds it (experts 0-5, experts 6-11; the router whole, the shared
    expert on both) gives two partial results; with the shared expert
    counted once they add up to the uncut reference's whole layer."""
    cfg, _ = tiny
    whole = dataclasses.replace(cfg, moe_experts_held=None)
    g = T.layer_groups(whole)["ssm_moe"][0]
    params = init_params(whole, jax.random.PRNGKey(11))
    lp = {k: v[2] for k, v in params["layers"]["ssm_moe"].items()}
    assert lp["w_gate"].shape[0] == 12
    h = jnp.asarray(np.random.default_rng(8).standard_normal((1, 40, 64)),
                    jnp.float32)
    s = R.spec(whole)
    assert s["held"] == (0, 12)
    want = R.experts(s, lp, h[0])
    shared = R.gated_mlp(h[0], lp["shared_w_gate"], lp["shared_w_up"],
                         lp["shared_w_down"])
    parts = []
    for first in (0, 6):
        share = dataclasses.replace(g, moe_experts_held=6,
                                    moe_expert_first=first)
        mine = {k: (v[first:first + 6] if k in ("w_gate", "w_up", "w_down")
                    else v) for k, v in lp.items()}
        out, _, counts = T._mlp(share, mine, h, jax.random.PRNGKey(0), True)
        assert counts.shape == (6,)
        parts.append(out[0])
    # every (token, expert) pair is computed by exactly one of the two
    np.testing.assert_allclose(parts[0] + parts[1] - shared, want,
                               rtol=1e-4, atol=1e-6)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-4


# ------------------------------------- the other families, through one _block

def test_falcon_h1s_parallel_block_still_matches_its_reference():
    from benchmark.lib import reference_falcon_h1 as RF

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b-d5.json")) as f:
        cfg = dataclasses.replace(system.transformer_config(
            json.load(f), rehearse=True), dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(3))
    toks = _tokens(cfg, 29, 6)
    got = T.forward(cfg, params, toks[None])[0]
    assert RF.rel_err(got, RF.reference_logits(cfg, params, toks)) < 1e-5


@pytest.mark.parametrize("drop", [False, True], ids=["dropless", "capacity"])
def test_a_parallel_block_takes_an_expert_layer_too(drop):
    """What ``_check_ssm`` refused before this PR and no configuration of the
    benchmark has: both mixers on one norm AND experts behind them.  The
    paged prefill and decode are the full forward's."""
    cfg = get_config(
        "falcon-h1-34b", num_layers=2, hidden_size=64, intermediate_size=48,
        num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=256, ssm_heads=4,
        ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=CHUNK,
        max_seq_len=256, num_experts=4, moe_top_k=2, moe_drop_tokens=drop,
        eval_capacity_factor=4.0, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = CausalLM(cfg)
    toks = _tokens(cfg, 30)[None]
    full = T.forward(cfg, params, toks)
    cache = model.init_paged_cache(4, PAGE, dtype=jnp.float32)
    table = jnp.arange(1, 4, dtype=jnp.int32)[None]
    prompt = jnp.zeros((1, 32), jnp.int32).at[:, :21].set(toks[:, :21])
    logits, cache = model.apply_paged(
        params, prompt, cache, table, jnp.zeros((1,), jnp.int32),
        (jnp.arange(32) < 21)[None])
    np.testing.assert_allclose(logits[0, :21], full[0, :21], atol=1e-6)
    for i in range(21, 30):
        logits, cache = model.apply_paged(
            params, toks[:, i:i + 1], cache, table,
            jnp.full((1,), i, jnp.int32), jnp.ones((1, 1), bool))
        np.testing.assert_allclose(logits[0, 0], full[0, i], atol=1e-6)


def test_mimos_two_kind_model_still_matches_its_reference():
    from benchmark.lib import reference_mimo_v2 as RM

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2.5-ep16-d7.json")) as f:
        cfg = dataclasses.replace(system.transformer_config(
            json.load(f), rehearse=True), dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(3))
    toks = _tokens(cfg, 29, 6)
    got = T.forward(cfg, params, toks[None])[0]
    assert RM.rel_err(got, RM.reference_logits(cfg, params, toks)) < 1e-4
    assert T.cache_kind(cfg)[0] == "window" and T.cache_layers(cfg) == (
        cfg.num_layers, 0)


# ----------------------------------------------------------- long prompts

def test_a_long_prompts_mixer_in_pieces_is_the_mixer_whole(monkeypatch):
    cfg = _rehearse_cfg(dtype=jnp.float32)
    g = T.layer_groups(cfg)["ssm_moe"][0]
    lp = {k: v[0] for k, v in init_params(
        cfg, jax.random.PRNGKey(2))["layers"]["ssm_moe"].items()}
    monkeypatch.setattr(SSM, "SSM_BLOCK_TOKENS", 32)
    h = jnp.asarray(np.random.default_rng(1).standard_normal((1, 128, 64)),
                    jnp.float32)
    for n_real in (128, 70, 33, 31, 1):
        mask = (jnp.arange(128) < n_real)[None]
        out, (state, tail) = SSM._ssm_mixer(g, lp, h, mask)
        want, (ws, wt) = SSM._ssm_mixer_block(g, lp, h, mask)
        np.testing.assert_allclose(out[:, :n_real], want[:, :n_real],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(state, ws, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(tail, wt, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------- the kernel in the tick

def test_a_tick_with_the_one_pass_step_is_the_xla_tick(monkeypatch):
    """The decode tick over layers walked by kind hands the state leaf and a
    Python ``row0`` to the one-pass kernel (interpret mode here) at a shape
    its tile plan takes; the tick is the ``jax.numpy`` step's."""
    cfg = _rehearse_cfg(dtype=jnp.float32, ssm_head_dim=8, ssm_heads=8,
                        ssm_state=128)
    params = init_params(cfg, jax.random.PRNGKey(1))
    model = CausalLM(cfg)
    B = 3
    cache = model.init_paged_cache(1 + B * 2, PAGE, dtype=jnp.float32,
                                   slots=B)
    rng = np.random.default_rng(0)
    cache["ssm_state"] = jnp.asarray(rng.standard_normal(
        cache["ssm_state"].shape), jnp.float32)
    table = jnp.arange(1, 1 + B * 2, dtype=jnp.int32).reshape(B, 2)
    args = (params, jnp.asarray(rng.integers(0, 256, (B, 1)), jnp.int32),
            cache, table, jnp.asarray([5, 0, 9], jnp.int32),
            jnp.asarray([[True], [True], [False]]))
    assert SSM.ssm_step_path(cfg) == "xla"
    want, want_cache = model.apply_paged(*args)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    assert SSM.ssm_step_path(cfg) == "one_pass"
    got, got_cache = model.apply_paged(*args)
    np.testing.assert_allclose(got[:2], want[:2], rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got_cache["ssm_state"],
                               want_cache["ssm_state"], rtol=1e-5, atol=1e-6)


# ------------------------------------------------------ the serving engine

@pytest.fixture(scope="module")
def served():
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)

    cfg = _rehearse_cfg(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(5))
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    engine = deepspeed_tpu.init_inference(
        model=CausalLM(cfg), params=params, dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))
    sv = engine.serving(b_slots=3, page_size=PAGE, max_model_len=64)
    rng = np.random.default_rng(1)
    requests = [Request(rid=f"r{i}", arrival_time=0.0,
                        max_new_tokens=int(rng.integers(3, 12)),
                        input_ids=rng.integers(0, 256, (int(
                            rng.integers(5, 31)),)).astype(np.int32))
                for i in range(8)]         # 8 requests over 3 slots: reuse
    configure_tracer(enabled=True, capacity=1 << 14)
    try:
        results = sv.run(requests)
        spans = [s for s in get_tracer().recorder.snapshot()
                 if isinstance(s, Span)]
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    return cfg, engine, sv, requests, results, spans


def test_the_engine_serves_it_through_the_same_scheduler(served):
    cfg, engine, sv, requests, results, _ = served
    assert len(results) == len(requests)
    by_rid = {q.rid: q for q in requests}
    # one compiled forward for every request: causal, so the padding behind
    # a sequence moves nothing before it
    forward = jax.jit(lambda p, t: T.forward(cfg, p, t)[0])
    for r in results:
        q = by_rid[r.rid]
        assert r.finish_reason == "length"
        ids = np.concatenate([q.input_ids,
                              np.asarray(r.output_ids[:-1], np.int32)])
        padded = np.zeros((1, 48), np.int32)
        padded[0, :len(ids)] = ids
        logits = forward(engine.params, jnp.asarray(padded))
        want = np.asarray(jnp.argmax(
            logits[len(q.input_ids) - 1:len(ids)], -1))
        assert list(r.output_ids) == want.tolist(), r.rid
    assert sv.page_accounting()["balanced"]
    inv = sv.program_inventory()
    assert inv["decode"] == 1 and inv["prefill_buckets"] == [16, 32]


def test_health_and_the_ready_line_say_what_the_cache_is_made_of(served):
    _, _, sv, _, _, _ = served
    health, info = sv.health(), sv._exec.mesh_info()
    for said in (health, info):
        assert (said["cache_kind"], said["kv_layers"],
                said["state_layers"]) == ("state", 1, 9)
    assert info["ssm_step"] == "xla" and info["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    layout = sv._layout
    assert layout.stateful and layout.block_attends_itself
    assert layout.pools == ((1 + 3 * 4, 4),) and not layout.ring_pages
    # a slot's state over the nine mamba layers only
    assert layout.state_slot_bytes == 9 * (4 * 32 * 16 * 4 + 3 * 160 * 4)


def test_the_spans_carry_counters_named_by_layers(served):
    _, _, _, requests, _, spans = served
    decode = [s.attrs for s in spans if s.name == "serve.decode"
              and s.attrs and "state_slots" in s.attrs]
    assert decode
    for a in decode:
        assert (a["state_layers"], a["kv_layers"]) == (9, 1)
        assert a["kv_live_rows"] == a["live_rows"] + a["state_slots"]
        assert a["state_bytes"] == a["state_slots"] * 9 * (
            4 * 32 * 16 * 4 + 3 * 160 * 4)
        assert a["pairs_total"] == a["state_slots"] * 3 * 10
        assert 0 < a["pairs_held"] <= a["pairs_total"]
        assert 0 < a["experts_touched_held"] <= 60
        # the older names keep their meaning
        assert (a["moe_pairs"], a["moe_local_pairs"],
                a["moe_experts_touched"]) == (
            a["pairs_total"], a["pairs_held"], a["experts_touched_held"])
    prefill = [s.attrs for s in spans if s.name == "serve.prefill"]
    assert len(prefill) == len(requests)
    for a in prefill:
        assert a["tokens"] <= a["bucket"] and a["gathered_rows"] == 0
        assert a["scan_chunks"] == -(-a["tokens"] // CHUNK)
        assert a["pairs_total"] == a["tokens"] * 3 * 10
        assert 0 < a["pairs_held"] < a["pairs_total"]


@pytest.mark.parametrize("mechanism,kw", [
    ("prefix sharing", {"prefix_cache": True}),
    ("KV-page tiering", {"host_tier_pages": 4}),
    ("the int8 pool", {"kv_dtype": "int8"}),
])
def test_the_engine_keeps_refusing_what_moves_pages(served, mechanism, kw):
    _, engine, _, _, _, _ = served
    with pytest.raises(NotImplementedError, match="state-space layers"):
        engine.serving(b_slots=2, page_size=PAGE, max_model_len=64, **kw)
