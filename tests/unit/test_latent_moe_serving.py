"""A model whose layers are ONE sublayer each (a state-space mixer, attention
or an expert layer alone) and whose routed experts work in a latent narrower
than the model (Nemotron-3-Super, ISSUE 61): the pattern's kind "mlp", the
one rule for which sublayers a layer has, a cache in which some layers own no
leaf, the squared ReLU, the latent's two projections, the grouped norm over
8 groups, the held share of the experts, and the serving engine over all of
it, at the benchmark's rehearsal size against the plain reference
(``benchmark/lib/reference_nemotron_h.py``)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_nemotron_h as R
from benchmark.lib import system
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PAGE, CHUNK = 16, 8
LETTERS = ["ssm", "mlp", "ssm", "mlp", "ssm", "mlp", "ssm", "full", "mlp",
           "ssm", "mlp"]                                    # MEMEMEM*EME


def _rehearse_cfg(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-super-ep4-d11.json")) as f:
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

def test_a_layer_is_one_sublayer_and_the_leaves_follow(tiny):
    cfg, params = tiny
    assert cfg.ssm_chunk == CHUNK and cfg.num_layers == 11
    plan = T.layer_plan(cfg)
    assert [kind for _, _, kind, _ in plan] == LETTERS
    assert [(g, i) for g, i, _, _ in plan] == R.plan(cfg)
    groups = T.layer_groups(cfg)
    assert {k: n for k, (_, n) in groups.items()} == {
        "ssm_only": 5, "mlp_moe": 5, "full_only": 1}
    # one rule: (attention, mixer, mlp) of each group
    assert T.sublayers(groups["ssm_only"][0]) == (False, True, False)
    assert T.sublayers(groups["full_only"][0]) == (True, False, False)
    assert T.sublayers(groups["mlp_moe"][0]) == (False, False, True)
    assert T.layers_by_kind(cfg) == {"ssm": 5, "mlp": 5, "full": 1}
    # ONE norm a layer and its own leaves alone
    ssm, mlp, full = (params["layers"][g] for g in
                      ("ssm_only", "mlp_moe", "full_only"))
    assert set(full) == {"attn_norm_scale", "wq", "wk", "wv", "wo"}
    assert set(ssm) == {"attn_norm_scale"} | {
        "ssm_" + n for n in ("in", "conv_w", "conv_b", "dt_bias", "A_log",
                             "D", "norm_scale", "out")}
    assert set(mlp) == {"mlp_norm_scale", "router", "router_bias", "w_in",
                        "w_down", "moe_latent_in", "moe_latent_out",
                        "shared_w_in", "shared_w_down"}
    # the experts on the latent's width, router and shared expert on the
    # model's; 8 of 32 held; ungated: two matrices
    assert mlp["router"].shape == (5, 64, 32)
    assert mlp["w_in"].shape == (5, 8, 32, 24)
    assert mlp["w_down"].shape == (5, 8, 24, 32)
    assert mlp["moe_latent_in"].shape == (5, 64, 32)
    assert mlp["moe_latent_out"].shape == (5, 32, 64)
    assert mlp["shared_w_in"].shape == (5, 64, 48)
    assert "lm_head" in params
    assert cfg.param_count == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert jax.tree_util.tree_structure(T.param_specs(cfg)) == \
        jax.tree_util.tree_structure(params)
    assert T.expert_counts_shape(cfg) == (5, 8)
    assert T.expert_products(cfg) == 2 * 5


def test_the_published_sizes_are_the_models():
    whole = get_config("nemotron-3-super-120b-a12b")
    assert len(whole.layer_pattern) == whole.num_layers == 88
    assert T.layers_by_kind(whole) == {"ssm": 40, "mlp": 40, "full": 8}
    assert whole.param_count == 120_668_707_840
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-super-ep4-d11.json")) as f:
        cut = system.transformer_config(json.load(f), rehearse=False)
    assert cut.param_count == 4_648_163_712
    assert T.cache_layers(cut) == (1, 5) and T.cache_depth(cut) == 1
    shapes = jax.eval_shape(lambda: T.init_paged_cache(
        cut, 3, 128, dtype=jnp.bfloat16, slots=2))
    # 2 KV heads of 128 are kept head-major; five state rows a slot
    assert T.pool_leaf_head_major(2, 128)
    assert shapes["k"].shape == (1, 3, 2, 128, 128)
    assert shapes["ssm_state"].shape == (5, 2, 128, 64, 128)
    assert shapes["ssm_conv"].shape == (5, 2, 3, 10240)
    assert 128 * 64 * 128 * 4 + 3 * 10240 * 2 == 4_255_744
    # a tick of 128 slots keeps lax.ragged_dot (5.5 rows an expert), a
    # 2,048-token chunk of a prompt is 88 deep and the kernel's where a
    # program may hold one
    assert T.expert_matmul_path(cut, 128, 1) == "ragged_dot"
    assert T._moe_chunks(cut, 1, 4096) == 2


def test_the_caches_leaves_cover_the_layers_that_have_them(tiny):
    cfg, _ = tiny
    assert T.cache_layers(cfg) == (1, 5) and T.cache_depth(cfg) == 1
    assert T.cache_kind(cfg)[0] == "state" and T.is_hybrid(cfg)
    cache = CausalLM(cfg).init_paged_cache(5, PAGE, dtype=jnp.bfloat16,
                                           slots=3)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 5, PAGE, 2, 16), "v": (1, 5, PAGE, 2, 16),
        "ssm_state": (5, 3, 4, 32, 16), "ssm_conv": (5, 3, 3, 192)}
    assert set(T.paged_cache_specs(cfg)) == set(cache)


REFUSED = {
    "an mlp entry without one_sublayer": (
        dict(one_sublayer=False), NotImplementedError,
        "mlp layers in one layer_pattern"),
    "one_sublayer without a pattern": (
        dict(layer_pattern=None), ValueError, "takes one"),
    "sandwich_norm": (dict(sandwich_norm=True), NotImplementedError,
                      "do not take sandwich_norm"),
    "parallel_residual": (dict(parallel_residual=True), NotImplementedError,
                          "do not take parallel_residual"),
    "four norms without a mixer": (
        dict(sandwich_norm=True, ssm_heads=0,
             layer_pattern=("full", "mlp") * 6), NotImplementedError,
        "one norm and one add"),
    "window layers": (dict(layer_pattern=("ssm", "window", "mlp", "full") * 3),
                      NotImplementedError, "window layers in one"),
    "a gelu shared expert": (dict(activation="gelu"), NotImplementedError,
                             "gated MLP"),
    "the capacity buffers": (dict(moe_drop_tokens=True), NotImplementedError,
                             "dropless"),
    "leading dense layers": (dict(dense_layers=2), NotImplementedError,
                             "dense_layers"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_what_is_not_built_is_refused_by_name(what):
    over, error, said = REFUSED[what]
    cfg = _rehearse_cfg(**over)
    with pytest.raises(error, match=said):
        init_params(cfg, jax.random.PRNGKey(0))


def test_training_refuses_by_the_caches_name(tiny):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="state-space layers"):
        T.forward(cfg, params, _tokens(cfg, 8)[None], deterministic=False)


# ------------------------------------------------- against the reference

def test_full_forward_is_the_references(tiny):
    cfg, params = tiny
    toks = _tokens(cfg, 37, 0)
    got = T.forward(cfg, params, toks[None])[0]
    assert R.rel_err(got, R.reference_logits(cfg, params, toks)) < 1e-5


MUTATIONS = {
    # what the repo had in each new thing's place
    "one norm over all channels": ({"norm_groups": 1}, "ssm_only"),
    "a plain ReLU": ({"square": False}, "mlp_moe"),
    "no latent projections": ({"latent": "identity"}, "mlp_moe"),
    "no shared expert": ({"shared": False}, "mlp_moe"),
    "another chip's experts": ({"held": (8, 8)}, "mlp_moe"),
}


@pytest.fixture(scope="module")
def one_layer_each(tiny):
    """The system's own ``_block`` for the last layer of each group over one
    seeded activation: ``{group: (leaves, its output)}`` and the input."""
    cfg, params = tiny
    h = jnp.asarray(np.random.default_rng(9).standard_normal((1, 24, 64)),
                    jnp.float32)
    pos = jnp.arange(24, dtype=jnp.int32)[None]
    out = {}
    for group, (g, n) in T.layer_groups(cfg).items():
        lp = {k: v[n - 1] for k, v in params["layers"][group].items()}
        attend = (T._attend_full(g, pos) if group == "full_only" else None)
        out[group] = (lp, T._block(g, lp, h, pos, jax.random.PRNGKey(0),
                                   attend, ssm=T._mixer_of(g))[0][0])
    return cfg, h[0], out


@pytest.mark.parametrize("group", ["ssm_only", "mlp_moe", "full_only"])
def test_one_layer_of_each_letter_is_the_references(one_layer_each, group):
    cfg, h, layers = one_layer_each
    lp, got = layers[group]
    assert R.layer_rel_err(got, R.block(R.spec(cfg), lp, h)[0]) < 1e-5


@pytest.mark.parametrize("what", list(MUTATIONS))
def test_a_mutated_reference_is_told_from_the_system(one_layer_each, what):
    """Each new thing matters: the reference with it replaced by what the
    repo had moves its layer's output by a thousand times what float32
    rounding does."""
    cfg, h, layers = one_layer_each
    mutate, group = MUTATIONS[what]
    lp, got = layers[group]
    want = R.block(R.spec(cfg, **mutate), lp, h)[0]
    assert R.layer_rel_err(got - h, want - h) > 1e-2


def test_a_layer_is_one_norm_and_one_add(tiny):
    """An E layer reads the sum the layer before it wrote: the reference in
    the parallel form the repo has (an E layer behind an M or * layer reading
    that layer's INPUT) is not the system."""
    cfg, params = tiny
    toks = _tokens(cfg, 37, 2)
    got = T.forward(cfg, params, toks[None])[0]
    assert R.layer_rel_err(got, R.reference_logits(
        cfg, params, toks, one_add=False)) > 1e-2


PAGED = [(21, 6), (40, 9)]     # a padded page; a prompt over two pages + 8


@pytest.mark.parametrize("n_prompt,n_decode", PAGED)
def test_prefill_then_paged_decode_is_the_references_full_forward(
        tiny, n_prompt, n_decode):
    """Logits, not tokens: the padded prompt's prefill into slot 1 of 3, then
    teacher-forced decode steps through the page of the * layer and the
    state rows of the five M layers (the five E layers own nothing); then
    the SAME slot again for another sequence."""
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
        assert float(jnp.abs(cache["ssm_state"][:, (0, 2)]).max()) == 0.0


def test_slots_of_unequal_length_tick_in_one_batch(tiny):
    """Three slots, two of them live at different lengths and one idle, in
    ONE decode tick: each live row's logits are its own sequence's."""
    cfg, params = tiny
    model = CausalLM(cfg)
    lens = (19, 7)
    cache = model.init_paged_cache(1 + 3 * 2, PAGE, dtype=jnp.float32,
                                   slots=3)
    table = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    seqs = [_tokens(cfg, n + 1, 10 + i) for i, n in enumerate(lens)]
    prefill = jax.jit(lambda c, t, row, mask, slot: model.apply_paged(
        params, t, c, row, jnp.zeros((1,), jnp.int32), mask,
        state_slot=slot)[1])
    for slot, (n, toks) in enumerate(zip(lens, seqs)):
        prompt = jnp.zeros((1, 2 * PAGE), jnp.int32).at[0, :n].set(toks[:n])
        cache = prefill(cache, prompt, table[slot:slot + 1],
                        (jnp.arange(2 * PAGE) < n)[None],
                        jnp.asarray([slot], jnp.int32))
    tick = jnp.asarray([[seqs[0][-1]], [seqs[1][-1]], [0]], jnp.int32)
    logits, cache = jax.jit(model.apply_paged)(
        params, tick, cache, table, jnp.asarray(lens + (0,), jnp.int32),
        jnp.asarray([[True], [True], [False]]))
    for slot, toks in enumerate(seqs):
        want = R.reference_logits(cfg, params, toks)[-1]
        assert R.rel_err(logits[slot, 0], want) < 1e-5
    assert float(jnp.abs(cache["ssm_state"][:, 2]).max()) == 0.0


def test_the_layer_checks_hold_one_layer_of_each_letter(tiny):
    cfg, params = tiny
    checks = R.layer_checks(cfg, params, seed=5, n_prompt=21, block_tokens=32,
                            n_decode=12, page_size=PAGE)
    assert set(checks) == {
        "mixer_layer", "attention_layer", "expert_layer",
        "router_near_tie_share", "state_after_prefill", "state_after_decode",
        "logits_after_decode", "other_slots_untouched"}
    for name, c in checks.items():
        assert c["rel_err"] <= min(c["tol"], 1e-5), (name, c)


def test_a_state_kept_in_bfloat16_reads_worse_than_one_in_float32(tiny):
    """The reference's own recurrence with its state rounded to bfloat16
    between two positions drifts from the one kept in float32: what the
    decode check's limit is set against on the chip."""
    cfg, params = tiny
    g = T.layer_groups(cfg)["ssm_only"][0]
    lp = {k: v[0] for k, v in params["layers"]["ssm_only"].items()}
    n = jnp.asarray(np.random.default_rng(4).standard_normal((64, 64)),
                    jnp.float32)
    want = R.mixer(R.spec(cfg), lp, n, keep=(63,))[1][0]
    low = R.mixer(R.spec(cfg, state_dtype=jnp.bfloat16), lp, n,
                  keep=(63,))[1][0]
    got = T._ssm_mixer(g, lp, n[None])[1][0][0]
    assert R.state_rel_err(got, want) < 1e-5
    assert R.state_rel_err(low, want) > 1e-3


def test_the_four_chips_shares_add_up_to_the_whole_layer(tiny):
    """The share tied to the model: the system's expert layer as each of the
    stage's four chips holds it (experts 0-7, 8-15, 16-23, 24-31; the router,
    the latent projections and the shared expert whole on each) gives four
    partial results, each through the up-projection; with the shared expert
    counted once they add up to the uncut reference's whole E layer."""
    cfg, _ = tiny
    whole = dataclasses.replace(cfg, moe_experts_held=None)
    g = T.layer_groups(whole)["mlp_moe"][0]
    params = init_params(whole, jax.random.PRNGKey(11))
    lp = {k: v[2] for k, v in params["layers"]["mlp_moe"].items()}
    assert lp["w_in"].shape == (32, 32, 24)
    h = jnp.asarray(np.random.default_rng(8).standard_normal((1, 40, 64)),
                    jnp.float32)
    s = R.spec(whole)
    assert s["held"] == (0, 32)
    want, _ = R.experts(s, lp, h[0])
    shared, _ = R.experts(dict(s, routed=False), lp, h[0])
    parts = []
    for first in (0, 8, 16, 24):
        share = dataclasses.replace(g, moe_experts_held=8,
                                    moe_expert_first=first)
        mine = {k: (v[first:first + 8] if k in ("w_in", "w_down") else v)
                for k, v in lp.items()}
        out, _, counts = T._mlp(share, mine, h, jax.random.PRNGKey(0), True)
        assert counts.shape == (8,)
        parts.append(out[0])
    # every (token, expert) pair is computed by exactly one of the four
    np.testing.assert_allclose(sum(parts) - 3 * shared, want,
                               rtol=1e-4, atol=1e-6)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-4


# ------------------------------------------------------ the serving engine

@pytest.fixture(scope="module")
def served():
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    cfg = _rehearse_cfg(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(5))
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


def test_health_and_the_ready_line_say_which_layers_hold_which_leaf(served):
    _, _, sv, _, _, _ = served
    health, info = sv.health(), sv._exec.mesh_info()
    for said in (health, info):
        assert (said["cache_kind"], said["kv_layers"],
                said["state_layers"]) == ("state", 1, 5)
    assert info["layers_by_kind"] == {"ssm": 5, "mlp": 5, "full": 1}
    assert info["cache_leaves_by_kind"] == {
        "ssm": ["ssm_state", "ssm_conv"], "mlp": [], "full": ["k", "v"]}
    assert info["ssm_step"] == "xla" and info["ssm_scan"] == "xla"
    assert info["state_programs"] == {"decode": "xla", "prefill_16": "xla",
                                      "prefill_32": "xla"}
    assert info["expert_matmul"] == {"decode": "ragged_dot",
                                     "prefill_16": "ragged_dot",
                                     "prefill_32": "ragged_dot"}
    assert info["kv_bytes_per_token"] == 2 * 2 * 16 * 4     # ONE * layer
    layout = sv._layout
    assert layout.stateful and layout.block_attends_itself
    # a slot's state over the five M layers only
    assert layout.state_slot_bytes == 5 * (4 * 32 * 16 * 4 + 3 * 192 * 4)


def test_the_spans_count_layers_by_kind(served):
    _, _, _, requests, _, spans = served
    decode = [s.attrs for s in spans if s.name == "serve.decode"
              and s.attrs and "state_slots" in s.attrs]
    assert decode
    for a in decode:
        assert (a["state_layers"], a["kv_layers"]) == (5, 1)
        assert a["layers_by_kind"] == "ssm:5,mlp:5,full:1"
        assert a["kv_live_rows"] == a["live_rows"] + a["state_slots"]
        assert a["state_bytes"] == a["state_slots"] * 5 * (
            4 * 32 * 16 * 4 + 3 * 192 * 4)
        # six pairs a token over the FIVE expert layers, a row a token a
        # layer through each latent projection
        assert a["pairs_total"] == a["state_slots"] * 6 * 5
        assert a["moe_latent_rows"] == a["state_slots"] * 5
        assert 0 < a["pairs_held"] <= a["pairs_total"]
        assert 0 < a["experts_touched_held"] <= a["moe_experts_held"] == 40
        assert (a["moe_kernel_products"], a["moe_ragged_products"]) == (0, 10)
    prefill = [s.attrs for s in spans if s.name == "serve.prefill"]
    assert len(prefill) == len(requests)
    for a in prefill:
        assert a["tokens"] <= a["bucket"] and a["gathered_rows"] == 0
        assert a["scan_chunks"] == -(-a["tokens"] // CHUNK)
        assert a["pairs_total"] == a["tokens"] * 6 * 5
        assert a["moe_latent_rows"] == a["tokens"] * 5
        assert 0 < a["pairs_held"] < a["pairs_total"]
        assert a["moe_sorted_rows"] >= a["moe_moved_rows"] > 0


@pytest.mark.parametrize("mechanism,kw", [
    ("prefix sharing", {"prefix_cache": True}),
    ("KV-page tiering", {"host_tier_pages": 4}),
    ("the int8 pool", {"kv_dtype": "int8"}),
])
def test_the_engine_keeps_refusing_what_moves_pages(served, mechanism, kw):
    _, engine, sv, _, _, _ = served
    with pytest.raises(NotImplementedError, match="state-space layers"):
        engine.serving(b_slots=2, page_size=PAGE, max_model_len=64, **kw)
    # LoRA adapters refuse the latent leaves with the rest (the per-slot
    # factors ride a scan over one stack of equal layers), they are not
    # carried; and generate()'s contiguous cache has no state row
    assert not sv._layout.allows("multi-tenant adapters")
    with pytest.raises(NotImplementedError, match="state-space layers"):
        engine.generate(jnp.zeros((1, 4), jnp.int32), max_new_tokens=2)
