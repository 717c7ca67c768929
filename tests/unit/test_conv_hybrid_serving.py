"""A model of gated short-convolution layers and grouped-query attention
layers in turn (LFM2-8B-A1B, ``lfm2_moe``: three "conv" layers in four whose
whole state is a two-row tail, QK-norm by head, two leading dense layers
before 32 experts top 4) through the model and the serving engine: both
against the plain reference, the terms a wrong build would leave out, the
tail's leaf, admission's reset, an idle slot, the three groups in one walk,
the span attrs, and the mechanisms that refuse such a model by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark.lib import reference_lfm2 as R
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models import mixers
from deepspeed_tpu.models.mixers import conv as CONV

from .test_ssm_serving import _is_greedy, _requests, _tokens

SERVE_KW = dict(b_slots=3, page_size=8, max_model_len=96)
# float32 on both sides: what is left is the order of the sums (a masked
# product against a softmax over the prefix, a grouped product against a sum
# over experts).  A bfloat16 anywhere reads 1e-3 or more on the same
# comparisons
F32_TOL = 2e-5


def tiny(**over):
    """Tiny widths, both leading dense layers and two whole periods (2 + 6
    conv layers, 2 attention layers), 8 experts of which a token takes 3."""
    kw = dict(num_layers=10, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_heads=4, num_kv_heads=2,
              head_dim=16, vocab_size=256, num_experts=8, moe_top_k=3,
              max_seq_len=512, dtype=jnp.float32)
    kw.update(over)
    return get_config("lfm2-8b-a1b", **kw)


@pytest.fixture(scope="module")
def params():
    return init_params(tiny(), jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def engine(params):
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    return deepspeed_tpu.init_inference(
        model=CausalLM(tiny()), params=params, dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))


def test_the_named_base_is_the_published_model_and_counts_its_parameters():
    cfg = get_config("lfm2-8b-a1b")
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size, cfg.num_heads,
            cfg.kv_heads, cfg.dims_per_head, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.norm_eps, cfg.max_seq_len,
            cfg.rope_theta) == (
        2048, 24, 65536, 32, 8, 64, 7168, 1792, 1e-5, 128000, 1e6)
    assert (cfg.conv_taps, cfg.conv_bias, cfg.dense_layers, cfg.num_experts,
            cfg.moe_top_k, cfg.moe_score_func, cfg.moe_select_bias,
            cfg.moe_norm_topk_prob, cfg.moe_norm_topk_eps,
            cfg.moe_routed_scale, cfg.moe_shared_experts,
            cfg.moe_drop_tokens) == (
        3, False, 2, 32, 4, "sigmoid", True, True, 1e-6, 1.0, 0, False)
    assert (cfg.position, cfg.qk_norm, cfg.tie_embeddings) == (
        "rope", "head", True)
    assert [i for i, k in enumerate(cfg.layer_pattern) if k == "full"] == [
        2, 6, 10, 14, 18, 21]
    assert set(cfg.layer_pattern) == {"conv", "full"}
    assert T.cache_layers(cfg) == (6, 18)
    groups = T.layer_groups(cfg)
    assert list(groups) == ["conv_dense", "full_moe", "conv_moe"]
    assert [n for _, n in groups.values()] == [2, 6, 16]
    # 18 x 16.78 + 6 x 10.49 + 2 x 44.04 + 22 x 352.39 + 134.22 M: the
    # published "8.3B" with the head tied
    assert cfg.param_count == 8_339_930_560
    assert get_config(cfg, num_layers=14).param_count == 4_667_077_376
    t = tiny()
    leaves = jax.eval_shape(lambda: init_params(t, jax.random.PRNGKey(0)))
    assert t.param_count == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(leaves))
    assert "lm_head" not in leaves
    dense, full, conv = (leaves["layers"][g] for g in groups)
    assert conv["conv_in"].shape == (6, 64, 3 * 64)
    assert conv["conv_w"].shape == (6, 3, 64)
    assert conv["conv_out"].shape == (6, 64, 64)
    assert "conv_b" not in conv
    assert conv["router_bias"].shape == (6, 8)
    # no attention leaf in a conv layer, no conv leaf in an attention layer;
    # the QK-norm's scales one head wide; no router in a dense layer
    assert not {"wq", "wk", "wv", "wo", "q_norm_scale"} & set(conv)
    assert not [k for k in full if k.startswith("conv_")]
    assert full["q_norm_scale"].shape == full["k_norm_scale"].shape == (2, 16)
    assert "router" not in dense and dense["w_gate"].shape == (2, 64, 96)
    assert full["w_gate"].shape == (2, 8, 64, 32)


def test_forward_is_the_reference(params):
    cfg, toks = tiny(), _tokens(29)
    want = R.reference_logits(cfg, params, toks[0])
    assert R.layer_rel_err(T.forward(cfg, params, toks)[0], want) < F32_TOL


@pytest.mark.parametrize("n_prompt,page", [(21, 16), (1, 8), (2, 8)],
                         ids=["padded-bucket", "shorter-than-the-taps",
                              "as-long-as-the-tail"])
def test_paged_prefill_then_eight_decode_ticks_are_the_reference(
        params, n_prompt, page):
    """The benchmark's own call: one row, a padded prompt at start 0 and
    then eight teacher-forced single tokens.  A prompt of one or two tokens
    has fewer rows than the two taps behind its last position reach: zeros
    stand before position 0."""
    from benchmark.traffic_kinds.serve_backlog import parity_paged

    class F32Cache(CausalLM):       # the harness asks for a bfloat16 pool
        def init_paged_cache(self, *a, dtype=None, **kw):
            return super().init_paged_cache(*a, dtype=jnp.float32, **kw)

    class Plain:                    # read as a sublayer's: no flip's room
        reference_logits = staticmethod(R.reference_logits)
        rel_err = staticmethod(R.layer_rel_err)

    err = parity_paged(Plain, F32Cache(tiny()), params, page, n_prompt, 8,
                       seed=5)
    assert max(err.values()) < F32_TOL, err


LEFT_OUT = {
    "qk-norm-over-the-whole-projection": ({"qk_norm": "whole"},
                                          "attention_operator"),
    "no-qk-norm": ({"qk_norm": None}, "attention_operator"),
    "an-activation-behind-the-convolution": ({"conv_act": True},
                                             "conv_operator"),
    "the-bias-in-the-gates": ({"bias_in_gate": True}, "expert_layer"),
    "three-experts-a-token-for-two": ({"top_k": 2}, "expert_layer"),
}


@pytest.mark.parametrize("term", list(LEFT_OUT))
def test_a_term_changed_fails_the_layers_own_check(params, term):
    """Each changed in a copy of the reference: the layer's own check, which
    passes at 2e-5, then reads over the limit it has where it judges (the
    published widths'; the toy widths are read against 3 x it), and the
    checks of the other kinds of layer stay where they were."""
    mutate, check = LEFT_OUT[term]
    checks = R.layer_checks(tiny(), params, 3, mutate=mutate)
    assert checks[check]["rel_err"] > checks[check]["tol"] / R.TOY_ROOM
    for other in {"attention_operator", "conv_operator", "expert_layer",
                  "dense_mlp"} - {check}:
        assert checks[other]["rel_err"] < F32_TOL, other


def test_the_selection_bias_changes_the_choice_and_not_the_gates(params):
    """With the drawn bias some tokens choose other experts than their
    scores alone would; the gates of the chosen are the scores there,
    renormalised over their sum + 1e-6, the bias in none of them."""
    cfg = tiny()
    g, n = T.layer_groups(cfg)["conv_moe"]
    s = R.spec(cfg)
    lp = R._layer(params, "conv_moe", n - 1)
    m = jnp.asarray(np.random.default_rng(4).standard_normal((64, 64)),
                    jnp.float32)
    gate, _ = R.expert_scores(s, lp, m)
    unbiased, _ = R.expert_scores(s, dict(lp, router_bias=0 * lp[
        "router_bias"]), m)
    moved = np.asarray(((gate > 0) != (unbiased > 0)).any(-1))
    assert 0 < moved.sum() < len(moved)
    score = np.asarray(jax.nn.sigmoid(m @ lp["router"]))
    chosen = np.asarray(gate > 0)
    assert (chosen.sum(-1) == cfg.moe_top_k).all()
    want = np.where(chosen, score, 0.0)
    want = want / (want.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(gate, want, rtol=1e-6)
    # ... and the system's layer is the reference's on every token
    checks = R.layer_checks(cfg, params, 3)
    assert checks["expert_layer"]["rel_err"] < F32_TOL
    assert checks["expert_masked_rows_zero"]["rel_err"] == 0.0


def test_qk_norm_by_head_differs_from_the_whole_projections(params):
    cfg = tiny()
    g, _ = T.layer_groups(cfg)["full_moe"]
    lp = {k: v[0] for k, v in params["layers"]["full_moe"].items()
          if k not in T._EXPERT_LEAVES}
    h = jnp.asarray(np.random.default_rng(1).standard_normal((1, 12, 64)),
                    jnp.float32)
    pos = jnp.arange(12)[None]
    q, k, _ = T._qkv(g, lp, h, pos)
    whole = T.get_config(g, qk_norm=True)
    lpw = dict(lp, q_norm_scale=jnp.ones((64,)), k_norm_scale=jnp.ones((32,)))
    qw, kw, _ = T._qkv(whole, lpw, h, pos)
    assert float(jnp.abs(q - qw).max()) > 1e-2
    assert float(jnp.abs(k - kw).max()) > 1e-2
    assert T.qk_norm_widths(g) == (16, 16)
    assert T.qk_norm_widths(whole) == (64, 32)
    # by head every head of q has unit mean square before the rotation,
    # which keeps it
    np.testing.assert_allclose(jnp.square(q).mean(-1), 1.0, rtol=5e-3)
    with pytest.raises(ValueError, match="False | True | 'head'"):
        init_params(tiny(qk_norm="heads"), jax.random.PRNGKey(0))


def test_layer_checks_pass_and_a_narrower_tail_fails_them(params):
    cfg = tiny()
    checks = R.layer_checks(cfg, params, 3)
    assert set(checks) == {
        "conv_operator", "attention_operator", "dense_mlp", "expert_layer",
        "expert_near_ties", "expert_masked_rows_zero", "tail_after_prefill",
        "tail_after_decode", "other_slots_untouched"}
    for name, c in checks.items():
        assert c["rel_err"] <= min(c["tol"], F32_TOL), (name, c)
    # the reference's z rounded through float8_e4m3 where the system keeps
    # the layer's own: over the published widths' limit on both readings
    narrow = R.layer_checks(cfg, params, 3,
                            mutate={"tail_dtype": jnp.float8_e4m3fn})
    for name in ("tail_after_prefill", "tail_after_decode"):
        assert narrow[name]["rel_err"] > R.TAIL_REL_TOL, name
    assert narrow["attention_operator"]["rel_err"] < F32_TOL


def test_a_bias_on_the_taps_joins_the_sum_before_the_gate_after():
    """``conv_bias`` (false in LFM2-8B-A1B, a key of the family's config):
    one more leaf, counted, added to the three-term sum."""
    cfg = tiny(conv_bias=True)
    leaves = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert leaves["layers"]["conv_moe"]["conv_b"].shape == (6, 64)
    assert cfg.param_count == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(leaves))
    g = T.layer_groups(cfg)["conv_moe"][0]
    rng = np.random.default_rng(0)
    lp = {"conv_in": rng.standard_normal((64, 192)).astype(np.float32) / 8,
          "conv_w": rng.uniform(-0.5, 0.5, (3, 64)).astype(np.float32),
          "conv_b": rng.standard_normal((64,)).astype(np.float32),
          "conv_out": np.eye(64, dtype=np.float32)}
    h = rng.standard_normal((1, 5, 64)).astype(np.float32)
    out, _ = CONV._conv_mixer(g, lp, jnp.asarray(h))
    p = h[0] @ lp["conv_in"]
    z = np.concatenate([np.zeros((2, 64), np.float32), p[:, :64] * p[:, 128:]])
    c = sum(z[k:k + 5] * lp["conv_w"][k] for k in range(3)) + lp["conv_b"]
    np.testing.assert_allclose(out[0], p[:, 64:128] * c, atol=1e-5)


def test_engine_serves_token_for_token_and_a_reused_slot_starts_clean(engine):
    """Seven requests through three slots, the three groups ``conv_dense``,
    ``full_moe`` and ``conv_moe`` in one walk: every slot is taken again by
    a request another just left, and each yields what greedy ``forward``
    yields, which is what it yields alone on a fresh engine (the decode
    lookahead on: a tick launched ahead is taken, none dropped)."""
    cfg = engine.model.config
    reqs = _requests(7)
    sv = engine.serving(**SERVE_KW)
    assert set(sv.params["layers"]) == {"conv_dense", "full_moe", "conv_moe"}
    assert sv._exec._pool_keys == ("k", "v", "conv_tail")
    info = sv._exec.mesh_info()
    assert (info["cache_kind"], info["kv_layers"], info["state_layers"],
            info["conv_step"], info["delta_step"], info["ssm_step"]) == (
        "state", 2, 8, "plain", None, None)
    assert sv._exec.moe_shape == (8, 8)
    assert not sv._grow         # the kind ``state`` keeps the reservation
    results = {r.rid: r for r in sv.run(reqs)}
    for q in reqs:
        out = results[q.rid].output_ids
        assert len(out) == q.max_new_tokens
        assert _is_greedy(cfg, engine.params, q.input_ids, out), q.rid
    h = sv.health()
    assert sv.page_accounting()["balanced"]
    assert h["lookahead_launched_total"] > 0
    assert h["lookahead_dropped_total"] == 0
    assert h["state_pool_bytes"] == 8 * 3 * 2 * 64 * 4
    alone = engine.serving(**SERVE_KW).run([reqs[5]])
    assert list(alone[0].output_ids) == list(results["r5"].output_ids)


def test_spans_carry_the_tail_and_name_no_scan(engine):
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)

    sv = engine.serving(**SERVE_KW)
    configure_tracer(enabled=True)
    try:
        sv.run(_requests(5, seed=3))
        spans = [s for s in get_tracer().recorder.snapshot()
                 if isinstance(s, Span)]
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    decode = [s.attrs for s in spans if s.name == "serve.decode"]
    prefill = [s.attrs for s in spans if s.name == "serve.prefill"]
    assert decode and len(prefill) == 5
    row = 8 * 2 * 64 * 4        # eight conv layers' tails, float32 here
    for a in decode:
        assert 1 <= a["state_slots"] <= 3
        assert a["state_bytes"] == a["state_slots"] * row
        assert a["state_passes"] == 1
        assert (a["state_layers"], a["kv_layers"]) == (8, 2)
        assert a["kv_live_rows"] == 2 * (a["live_rows"] + a["state_slots"])
        # the program's expert counts, as every dropless model's
        assert a["moe_experts_held"] == 8 * 8
        assert a["moe_rows"] == a["moe_live_rows"] == (
            a["state_slots"] * 3 * 8)
    for a in prefill:
        assert a["state_reset"] == 1 and a["gathered_rows"] == 0
        assert "scan_chunks" not in a and "scan_chunks_bucket" not in a
        assert a["pairs_held"] == a["tokens"] * 3 * 8


def test_the_refusals_that_stay_for_the_other_state_kinds():
    """Leading dense layers before a state kind are built for "conv" and
    for no other; expert layers behind a delta layer are still refused; no
    two state kinds share a pattern."""
    granite = get_config(
        "granite-4.0-h-small", num_layers=6, hidden_size=64,
        intermediate_size=24, num_heads=4, num_kv_heads=2, head_dim=16,
        vocab_size=256, ssm_heads=4, ssm_head_dim=32, ssm_state=16,
        ssm_chunk=8, num_experts=4, moe_top_k=2, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="leading dense layers"):
        mixers.check(get_config(granite, dense_layers=1))
    with pytest.raises(NotImplementedError, match="conv layers in one"):
        mixers.check(get_config(granite, layer_pattern=("ssm", "conv",
                                                        "full") * 2))
    olmo = get_config(
        "olmo-hybrid-7b", num_layers=8, hidden_size=64, intermediate_size=96,
        num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=256,
        linear_heads=4, linear_key_dim=8, linear_value_dim=64,
        linear_chunk=8, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="leading dense layers"):
        mixers.check(get_config(olmo, dense_layers=1))
    with pytest.raises(NotImplementedError, match="expert layers"):
        mixers.check(get_config(olmo, num_experts=4))
    with pytest.raises(NotImplementedError, match="conv layers in one"):
        mixers.check(get_config(olmo, layer_pattern=(
            "linear", "conv", "linear", "full") * 2))
    # what is lifted: a dense group under a pattern with the tail's kind
    assert mixers.check(tiny()) is None
    assert tiny().dense_layers == 2
