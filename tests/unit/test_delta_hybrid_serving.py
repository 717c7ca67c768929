"""A model of gated-delta-rule layers and full-attention layers in turn
(Olmo-Hybrid, ``olmo_hybrid``: three "linear" layers in four, a matrix state
a head, the norm on each branch's output) through the model and the serving
engine: the chunk form against the recurrence and both against the plain
reference, the four terms a wrong build would leave out, a state kept in
bfloat16, the cache's leaves, admission's reset, the decode lookahead, the
span attrs, and the mechanisms that refuse such a model by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark.lib import reference_olmo_hybrid as R
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.mixers import common as MX
from deepspeed_tpu.models.mixers import delta as DELTA

from .test_ssm_serving import (_deadline_mix, _drive, _is_greedy,
                               _requests, _tokens)

SERVE_KW = dict(b_slots=3, page_size=8, max_model_len=96)
# float32 on both sides: what is left is the order of the sums (the chunk
# form against the recurrence, a masked product against a softmax over the
# prefix).  A bfloat16 anywhere the configuration says float32 reads 1e-3 or
# more on the same comparisons
F32_TOL = 2e-5


def tiny(**over):
    """Tiny widths, two whole periods (6 delta + 2 attention layers), keys
    8 wide and values 64 (two heads a leaf row), chunks of 8."""
    kw = dict(num_layers=8, hidden_size=64, intermediate_size=96,
              num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=256,
              linear_heads=4, linear_key_dim=8, linear_value_dim=64,
              linear_chunk=8, max_seq_len=512, dtype=jnp.float32)
    kw.update(over)
    return get_config("olmo-hybrid-7b", **kw)


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
    cfg = get_config("olmo-hybrid-7b")
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size, cfg.num_heads,
            cfg.kv_heads, cfg.dims_per_head, cfg.intermediate_size,
            cfg.norm_eps, cfg.max_seq_len) == (
        3840, 32, 100352, 30, 30, 128, 11008, 1e-6, 65536)
    assert (cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim,
            cfg.linear_conv, cfg.linear_chunk, cfg.linear_neg_eigval) == (
        30, 96, 192, 4, 64, True)
    assert (cfg.position, cfg.qk_norm, cfg.norm_after,
            cfg.tie_embeddings) == ("none", True, True, False)
    assert cfg.layer_pattern == ("linear", "linear", "linear", "full") * 8
    assert DELTA.delta_widths(cfg) == (2880, 5760, 11520)
    assert DELTA.delta_in_width(cfg) == 17280
    assert T.cache_layers(cfg) == (8, 24)
    groups = T.layer_groups(cfg)
    assert list(groups) == ["linear_dense", "full_dense"]
    linear, full = (g.param_count - get_config(g, num_layers=0).param_count
                    for g, _ in groups.values())
    assert (linear // 24, full // 8) == (215_570_172, 185_809_920)
    assert cfg.param_count == 7_430_870_688
    assert get_config(cfg, num_layers=16).param_count == 4_100_788_944
    t = tiny()
    leaves = jax.eval_shape(lambda: init_params(t, jax.random.PRNGKey(0)))
    assert t.param_count == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(leaves))
    lin, att = leaves["layers"]["linear_dense"], leaves["layers"]["full_dense"]
    assert lin["delta_in"].shape == (6, 64, 2 * 32 + 2 * 256)
    assert lin["delta_ba"].shape == (6, 64, 8)
    assert lin["delta_conv_w"].shape == (6, 4, 2 * 32 + 256)
    assert lin["delta_norm_scale"].shape == (6, 64)
    # no attention leaf in a delta layer, no delta leaf in an attention
    # layer; QK-norm under a layer_pattern is the attention layers' alone
    assert not {"wq", "wk", "wv", "wo", "q_norm_scale"} & set(lin)
    assert {"wq", "q_norm_scale", "k_norm_scale"} <= set(att)
    assert not [k for k in att if k.startswith("delta_")]
    # two norms a layer, both on a branch's output
    assert {k for k in lin if "norm" in k} == {
        "attn_norm_scale", "mlp_norm_scale", "delta_norm_scale"}


def test_the_drawn_gates_cover_both_signs_and_decay(params):
    """``beta`` reaches (1, 2), the half ``linear_allow_neg_eigval`` adds,
    and ``alpha`` < 1, or neither the factor 2 nor the gate is tested."""
    cfg = tiny()
    g = T.layer_groups(cfg)["linear_dense"][0]
    lp = {k: v[3] for k, v in params["layers"]["linear_dense"].items()}
    h = jnp.asarray(np.random.default_rng(0).standard_normal((1, 64, 64)),
                    jnp.float32)
    _, _, b, a = DELTA._delta_project(g, lp, h)
    beta = 2 * jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(lp["delta_A_log"])
                    * jax.nn.softplus(a + lp["delta_dt_bias"]))
    assert float(beta.max()) > 1.1 and float(beta.min()) < 0.9
    assert float((beta > 1).mean()) > 0.25
    assert float(alpha.max()) < 1.0 and float(alpha.min()) > 0.0
    assert float(alpha.min()) < 0.99


def test_forward_is_the_reference(params):
    cfg, toks = tiny(), _tokens(29)
    want = R.reference_logits(cfg, params, toks[0])
    assert R.rel_err(T.forward(cfg, params, toks)[0], want) < F32_TOL


def test_paged_prefill_then_eight_decode_ticks_are_the_reference(params):
    """The benchmark's own call: one row, a padded prompt at start 0 (21
    tokens: two chunks of 8 crossed, the third cut) and then eight
    teacher-forced single tokens."""
    from benchmark.traffic_kinds.serve_backlog import parity_paged

    class F32Cache(CausalLM):       # the harness asks for a bfloat16 pool
        def init_paged_cache(self, *a, dtype=None, **kw):
            return super().init_paged_cache(*a, dtype=jnp.float32, **kw)

    err = parity_paged(R, F32Cache(tiny()), params, 16, 21, 8, seed=5)
    assert max(err.values()) < F32_TOL, err


LEFT_OUT = {"the-l2-norm-of-q-and-k": {"l2norm": False},
            "the-factor-2-of-allow-neg-eigval": {"beta_scale": 1.0},
            "the-gated-norm-by-head": {"gate_norm": False},
            "the-convolutions-silu": {"conv_silu": False}}


@pytest.mark.parametrize("term", list(LEFT_OUT))
def test_a_term_left_out_fails_the_comparison(params, term):
    """Each of the four dropped in a copy of the reference: the comparison
    that passes at 2e-5 then reads over the benchmark's own limit (0.05),
    and the delta layer's own check over the limit it has where it judges
    (the published widths'; the toy widths are read against 3 x it)."""
    from benchmark.traffic_kinds.serve_backlog import LOGITS_REL_TOL

    cfg, toks = tiny(), _tokens(29)
    got = T.forward(cfg, params, toks)[0]
    wrong = R.reference_logits(cfg, params, toks[0], **LEFT_OUT[term])
    assert R.rel_err(got, wrong) > LOGITS_REL_TOL
    checks = R.layer_checks(cfg, params, 3, mutate=LEFT_OUT[term])
    assert checks["linear_layer_block"]["rel_err"] > (
        checks["linear_layer_block"]["tol"] / R.TOY_ROOM)
    assert checks["attention_layer_block"]["rel_err"] < F32_TOL


def test_layer_checks_pass_and_a_bfloat16_state_fails_them(params):
    cfg = tiny()
    checks = R.layer_checks(cfg, params, 3)
    assert set(checks) == {
        "linear_layer_block", "attention_layer_block", "state_after_prefill",
        "state_after_decode", "logits_after_decode", "other_slots_untouched"}
    for name, c in checks.items():
        assert c["rel_err"] <= min(c["tol"], F32_TOL), (name, c)
    # the state rounded to bfloat16 between two positions, 40 steps behind a
    # prompt of 45: over the tight reading by two orders, and over the
    # published widths' limit (the toy widths are read against 3 x it)
    narrow = R.layer_checks(cfg, params, 3,
                            mutate={"state_dtype": jnp.bfloat16})
    got = narrow["state_after_decode"]
    assert got["rel_err"] > 100 * F32_TOL
    assert got["rel_err"] > got["tol"] / R.TOY_ROOM / 2
    assert narrow["attention_layer_block"]["rel_err"] < F32_TOL


@pytest.mark.parametrize("length,carried", [(13, False), (29, True),
                                             (8, False), (1, True)])
def test_the_chunk_form_is_the_one_step_recurrence(length, carried):
    """Over a length that is no multiple of the chunk (8), from zeros or
    from a state carried in."""
    cfg = tiny()
    H, dk, dv = 4, 8, 64
    rng = np.random.default_rng(length)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = unit(draw(2, length, H, dk)) * dk ** -0.5, unit(draw(2, length, H, dk))
    v = draw(2, length, H, dv)
    g = -jax.nn.softplus(draw(2, length, H))
    beta = 2 * jax.nn.sigmoid(draw(2, length, H))
    s0 = draw(2, H, dk, dv) if carried else jnp.zeros((2, H, dk, dv))
    o, s = DELTA._delta_scan(cfg, q, k, v, g, beta, s0)
    state, os_ = s0, []
    for t in range(length):
        o_t, state = DELTA._delta_step(
            cfg, q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], g[:, t:t + 1],
            beta[:, t:t + 1], state)
        os_.append(o_t)
    np.testing.assert_allclose(o, jnp.concatenate(os_, 1), atol=2e-5)
    np.testing.assert_allclose(s, state, atol=2e-5)


def test_the_substitution_inverts_a_matrix_whose_powers_overflow():
    """Identical keys at full write strength: ``A`` is 2 under the diagonal,
    ``A^32`` has entries past 1e20, and the inverse is +-2."""
    C = 64
    A = jnp.tril(jnp.full((C, C), 2.0), -1)
    T_inv = DELTA._unit_lower_inverse(A) + jnp.eye(C)
    np.testing.assert_allclose(T_inv @ (jnp.eye(C) + A), jnp.eye(C),
                               atol=1e-4)
    assert float(jnp.abs(T_inv).max()) == 2.0


def test_engine_serves_token_for_token_and_a_reused_slot_starts_clean(engine):
    """Nine requests through three slots: every slot is taken again by a
    request another just left, and each yields what greedy ``forward``
    yields, which is what it yields alone on a fresh engine."""
    cfg = engine.model.config
    reqs = _requests(9)
    sv = engine.serving(**SERVE_KW)
    assert sv._exec._pool_keys == ("k", "v", "delta_state", "delta_conv")
    info = sv._exec.mesh_info()
    assert (info["cache_kind"], info["kv_layers"], info["state_layers"],
            info["delta_step"], info["ssm_step"]) == (
        "state", 2, 6, "plain", None)
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
    assert h["state_pool_bytes"] == 6 * 3 * (4 * 8 * 64 * 4 + 3 * 320 * 4)
    alone = engine.serving(**SERVE_KW).run([reqs[7]])
    assert list(alone[0].output_ids) == list(results["r7"].output_ids)


def test_lookahead_on_is_lookahead_off_with_a_slot_expired_in_flight(engine):
    plain, _ = _drive(engine.serving(lookahead=False, **SERVE_KW),
                      _deadline_mix())
    sv = engine.serving(**SERVE_KW)
    ahead, _ = _drive(sv, _deadline_mix())
    assert ahead == plain
    assert 0 < len(plain["r0"]) < 24
    h = sv.health()
    assert h["deadline_expired_total"] == 1
    assert h["lookahead_stale_taken_total"] > 0
    assert h["lookahead_dropped_total"] == 0
    assert sv.page_accounting()["balanced"]


def test_dropping_a_launched_tick_would_advance_the_state_twice(engine):
    def reqs():
        return _requests(3, seed=7, new=(24, 30))

    plain, _ = _drive(engine.serving(lookahead=False, **SERVE_KW), reqs())
    sv = engine.serving(**SERVE_KW)
    for q in reqs():
        sv.submit(q)
    for now in range(5):
        sv.step(now=float(now))
    assert len(sv._ahead) > 1
    sv._ahead.clear()       # what no path of the engine does
    dropped, _ = _drive(sv, [], start=5.0)
    assert any(dropped[r] != plain[r] for r in plain)
    kept, _ = _drive(engine.serving(**SERVE_KW), reqs())
    assert kept == plain


def _spans(sv, requests):
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)

    configure_tracer(enabled=True)
    try:
        results = {r.rid: list(r.output_ids) for r in sv.run(requests)}
        spans = [s for s in get_tracer().recorder.snapshot()
                 if isinstance(s, Span)]
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    return results, spans


def test_spans_carry_the_state_and_the_chunks(engine):
    _, spans = _spans(engine.serving(**SERVE_KW), _requests(5, seed=3))
    decode = [s.attrs for s in spans if s.name == "serve.decode"]
    prefill = [s.attrs for s in spans if s.name == "serve.prefill"]
    assert decode and len(prefill) == 5
    row = 6 * (4 * 8 * 64 * 4 + 3 * 320 * 4)
    for a in decode:
        assert 1 <= a["state_slots"] <= 3
        assert a["state_bytes"] == a["state_slots"] * row
        assert a["state_passes"] == 3       # the CPU's tick: _delta_step
        assert (a["state_layers"], a["kv_layers"]) == (6, 2)
        # the row being written counted in, over the two attention layers
        assert a["kv_live_rows"] == 2 * (a["live_rows"] + a["state_slots"])
    for a in prefill:
        assert a["state_reset"] == 1 and a["gathered_rows"] == 0
        assert a["scan_chunks"] == -(-a["tokens"] // 8)
        assert a["scan_chunks_bucket"] == a["bucket"] // 8
    assert (sum(a["scan_chunks"] for a in prefill)
            < sum(a["scan_chunks_bucket"] for a in prefill))


def test_the_one_pass_step_serves_token_for_token(engine, monkeypatch):
    """The decode tick with the kernel in it (interpret mode: the test
    answers in the backend's place, as a TPU would) against the engine whose
    tick holds ``_delta_step``: the same tokens, greedy ``forward``'s, and
    each engine's spans say how many passes its tick makes over the state."""
    cfg = engine.model.config

    def serve(step, passes):
        sv = engine.serving(**SERVE_KW)
        assert sv._exec.mesh_info()["delta_step"] == step
        assert sv.health()["delta_step"] == step
        results, spans = _spans(sv, _requests(9))
        decode = [s.attrs for s in spans if s.name == "serve.decode"]
        assert decode and all(a["state_passes"] == passes for a in decode)
        assert sv.page_accounting()["balanced"]
        assert sv.health()["lookahead_dropped_total"] == 0
        return results

    plain = serve("plain", 3)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    one_pass = serve("one_pass", 1)
    assert one_pass == plain
    for q in _requests(9):
        assert len(one_pass[q.rid]) == q.max_new_tokens
        assert _is_greedy(cfg, engine.params, q.input_ids, one_pass[q.rid])

