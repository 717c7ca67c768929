"""A model of gated attention over window rings (rotary) and full layers
without positions, four norms a layer, a leading dense layer and then a held
share of sigmoid-routed experts beside a shared one (Trinity-Large-Preview,
``afmoe``) through the model and the serving engine: both against the plain
reference, with contexts under, at and past the window in one batch; the
gate, the position by kind and each norm shown to matter; the shares tied to
the whole layer; the window walked in chunks; the span attrs at a ring of
several pages; and the mechanisms that refuse such a model by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark.lib import reference_afmoe as R
from deepspeed_tpu.inference.execution import MeshExecutor
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T

from .test_hybrid_serving import REFUSALS
from .test_ssm_serving import _is_greedy

PAGE, WINDOW = 8, 16
SERVE_KW = dict(b_slots=3, page_size=PAGE, max_model_len=96)
# float32 on both sides: what is left is the order of the sums
F32_TOL = 2e-5
# published layers 5-12: sliding (dense), sliding, full, sliding x3, full,
# sliding: two whole periods
PATTERN = get_config("trinity-large-preview").layer_pattern[5:13]
NORMS = ("attn_norm_scale", "attn_post_norm_scale", "mlp_norm_scale",
         "mlp_post_norm_scale", "q_norm_scale", "k_norm_scale")


def tiny(**over):
    """Tiny widths, the cell's eight layers (one dense, two whole periods,
    six window layers to two full), 4 held of 16 experts, 3 a token, a
    window of two pages."""
    kw = dict(num_layers=8, layer_pattern=PATTERN, dense_layers=1,
              hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
              num_heads=8, num_kv_heads=2, head_dim=16, window_size=WINDOW,
              num_experts=16, moe_experts_held=4, moe_top_k=3, vocab_size=256,
              max_seq_len=512, embed_multiplier=8.0, dtype=jnp.float32)
    kw.update(over)
    return get_config("trinity-large-preview", **kw)


def _seeded(cfg, seed=1):
    """Seeded weights with every norm's scale drawn too: at 1 a norm that
    is left out of the block still agrees in part."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)

    def draw(path, a):
        if path[-1].key in NORMS or path[-1].key == "final_norm_scale":
            return a * jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(draw, params)


@pytest.fixture(scope="module")
def params():
    return _seeded(tiny())


@pytest.fixture(scope="module")
def engine(params):
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    return deepspeed_tpu.init_inference(
        model=CausalLM(tiny()), params=params, dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 256, (1, n)).astype(np.int32))


def _requests(lengths, seed=0, new=(6, 30)):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", arrival_time=0.0,
                    max_new_tokens=int(rng.integers(*new)),
                    input_ids=rng.integers(0, 256, (int(n),)).astype(np.int32))
            for i, n in enumerate(lengths)]


def test_the_named_base_is_the_published_model_and_counts_its_parameters():
    cfg = get_config("trinity-large-preview")
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size, cfg.num_heads,
            cfg.kv_heads, cfg.dims_per_head, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.norm_eps, cfg.max_seq_len,
            cfg.rope_theta, cfg.window_size) == (
        3072, 60, 200192, 48, 8, 128, 12288, 3072, 1e-5, 262144, 1e4, 4096)
    assert (cfg.position, cfg.window_position, cfg.qk_norm,
            cfg.attn_output_gate, cfg.sandwich_norm, cfg.tie_embeddings) == (
        "none", "rope", "head", True, True, False)
    assert cfg.embed_multiplier == pytest.approx(3072 ** 0.5)
    assert (cfg.dense_layers, cfg.num_experts, cfg.moe_top_k,
            cfg.moe_score_func, cfg.moe_select_bias, cfg.moe_norm_topk_prob,
            cfg.moe_norm_topk_eps, cfg.moe_routed_scale,
            cfg.moe_shared_experts, cfg.moe_drop_tokens) == (
        6, 256, 4, "sigmoid", True, True, 1e-20, 2.448, 1, False)
    assert [i for i, k in enumerate(cfg.layer_pattern) if k == "full"] == list(
        range(3, 60, 4))
    assert list(T.layer_groups(cfg)) == ["window_dense", "full_dense",
                                         "window_moe", "full_moe"]
    # the position rule by kind is the group's own: what _qkv reads
    groups = T.layer_groups(cfg)
    assert {n: g.position for n, (g, _) in groups.items()} == {
        "window_dense": "rope", "full_dense": "none", "window_moe": "rope",
        "full_moe": "none"}
    # a ring of 33 pages a slot at the cell's pages of 128 rows
    assert T.window_ring_pages(cfg.window_size, 128) == 33
    # the cell's cut: published layers 5-12, 16 of 256 experts, an eighth of
    # the vocabulary.  Attention 62,914,816 a layer (the gate included), the
    # norms 12,288, the dense MLP 113,246,208, an expert 28,311,552, the
    # router 786,688 (ISSUE 58's sum counts the QK-norm's 256 twice a layer
    # and reads 4,144,997,120)
    cut = get_config(cfg, num_layers=8, layer_pattern=PATTERN,
                     dense_layers=1, moe_experts_held=16, vocab_size=25024)
    assert cut.param_count == 4_144_995_072
    assert {n: k for n, (_, k) in T.layer_groups(cut).items()} == {
        "window_dense": 1, "window_moe": 5, "full_moe": 2}
    t = tiny()
    leaves = jax.eval_shape(lambda: init_params(t, jax.random.PRNGKey(0)))
    assert t.param_count == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(leaves))
    dense, window, full = (leaves["layers"][g] for g in (
        "window_dense", "window_moe", "full_moe"))
    for lp, n in ((dense, 1), (window, 5), (full, 2)):
        assert lp["wg"].shape == lp["wq"].shape == (n, 64, 128)
        assert lp["q_norm_scale"].shape == lp["k_norm_scale"].shape == (n, 16)
        assert lp["attn_post_norm_scale"].shape == (n, 64)
    assert "router" not in dense and dense["w_gate"].shape == (1, 64, 96)
    assert window["w_gate"].shape == (5, 4, 64, 32)
    assert window["shared_w_gate"].shape == (5, 64, 32)
    assert window["router"].shape == (5, 64, 16)
    specs = T.param_specs(t)["layers"]["full_moe"]
    assert specs["wg"] == specs["wq"]
    with pytest.raises(ValueError, match="window_position"):
        T.layer_groups(tiny(window_position="learned"))


def test_forward_is_the_reference(params):
    cfg, toks = tiny(), _tokens(45)
    want = R.reference_logits(cfg, params, toks[0])
    assert R.layer_rel_err(T.forward(cfg, params, toks)[0], want) < F32_TOL


def _paged_batch(cfg, params, prompts, n_decode, dtype=jnp.float32):
    """``prompts`` token rows through ``apply_paged`` as the engine calls it:
    each prompt alone into its slot's pages and ring (a block of one row),
    then ``n_decode`` teacher-forced ticks of ALL the slots at once through
    both pools; the reference over each whole sequence.  Returns
    ``[(prefill error, worst decode error)]`` a sequence."""
    model = CausalLM(cfg)
    B, maxp = len(prompts), 8
    R_pages = T.window_ring_pages(cfg.window_size, PAGE)
    cache = model.init_paged_cache(1 + B * maxp, PAGE, dtype=dtype,
                                   window_pages=1 + B * R_pages)
    full = jnp.arange(1, 1 + B * maxp, dtype=jnp.int32).reshape(B, maxp)
    ring = jnp.arange(1, 1 + B * R_pages, dtype=jnp.int32).reshape(B, R_pages)
    step = jax.jit(model.apply_paged)
    seqs = [np.asarray(p) for p in prompts]
    want = [np.asarray(R.reference_logits(cfg, params, jnp.asarray(s)))
            for s in seqs]
    errs = []
    for b, s in enumerate(seqs):
        n = len(s) - n_decode
        pad = -(-n // PAGE) * PAGE
        block = jnp.zeros((1, pad), jnp.int32).at[0, :n].set(s[:n])
        logits, cache = step(params, block, cache,
                             (full[b:b + 1], ring[b:b + 1]),
                             jnp.zeros((1,), jnp.int32),
                             (jnp.arange(pad) < n)[None])
        errs.append([R.layer_rel_err(logits[0, :n], want[b][:n]), 0.0])
    for i in range(n_decode):
        at = [len(s) - n_decode + i for s in seqs]
        toks = jnp.asarray([[s[a]] for s, a in zip(seqs, at)], jnp.int32)
        logits, cache = step(params, toks, cache, (full, ring),
                             jnp.asarray(at, jnp.int32),
                             jnp.ones((B, 1), bool))
        for b, a in enumerate(at):
            errs[b][1] = max(errs[b][1],
                             R.layer_rel_err(logits[b, 0], want[b][a]))
    return errs


def test_prefill_then_ticks_under_at_and_past_the_window_in_one_batch(params):
    """Three slots in every tick: one that stays under the window of 16 (a
    window layer reads what a full one does), one whose prompt ends AT it
    and whose first tick crosses it, and one far past it, whose ring of
    three pages wrapped inside its prompt."""
    cfg = tiny()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, (n + 6,)) for n in (5, 16, 41)]
    errs = _paged_batch(cfg, params, prompts, n_decode=6)
    assert np.max(errs) < F32_TOL, errs


def test_the_benchmarks_own_parity_call_agrees(params):
    """One row, a prompt padded to whole pages that wraps the ring, then six
    teacher-forced tokens: ``serve-backlog``'s check, with a float32 pool."""
    from benchmark.traffic_kinds.serve_backlog import parity_paged

    class F32Cache(CausalLM):       # the harness asks for a bfloat16 pool
        def init_paged_cache(self, *a, dtype=None, **kw):
            return super().init_paged_cache(*a, dtype=jnp.float32, **kw)

    class Plain:                    # read as a sublayer's: no flip's room
        reference_logits = staticmethod(R.reference_logits)
        rel_err = staticmethod(R.layer_rel_err)

    err = parity_paged(Plain, F32Cache(tiny()), params, PAGE, 45, 6, seed=5)
    assert max(err.values()) < F32_TOL, err


LEFT_OUT = {
    "no-gate": {"gate": False},
    "no-position-in-a-window-layer": {"rotate": {"window": False,
                                                 "full": False}},
    "rotary-in-a-full-layer-too": {"rotate": {"window": True, "full": True}},
    "no-qk-norm": {"qk_norm": False},
    "no-norm-behind-the-branches": {"post_norms": False},
    "a-window-one-row-short": {"window": WINDOW - 1},
    "the-embedding-unscaled": {"embed_scale": 1.0},
    "gates-unscaled": {"routed_scale": 1.0},
    "no-shared-expert": {"shared": False},
    "two-experts-a-token-for-three": {"top_k": 2},
    "the-bias-in-the-gates": {"bias_in_gate": True},
}


@pytest.mark.parametrize("term", list(LEFT_OUT))
def test_a_term_left_out_of_the_equations_is_seen(params, term):
    """Each changed in a copy of the reference: the system, which agrees
    with the equations at 2e-5, is then over a hundred times further."""
    cfg, toks = tiny(), _tokens(45)
    got = T.forward(cfg, params, toks)[0]
    off = R.reference_logits(cfg, params, toks[0], **LEFT_OUT[term])
    assert R.layer_rel_err(got, off) > 100 * F32_TOL


@pytest.mark.parametrize("norm", NORMS)
def test_each_norm_matters(params, norm):
    """The system with ONE norm's learned scales at 1 against the reference
    with the drawn ones: each of the four norms of a layer and the two of
    the QK-norm is in the block."""
    cfg, toks = tiny(), _tokens(45)
    flat = {g: {k: (jnp.ones_like(v) if k == norm else v)
                for k, v in lp.items()}
            for g, lp in params["layers"].items()}
    got = T.forward(cfg, {**params, "layers": flat}, toks)[0]
    want = R.reference_logits(cfg, params, toks[0])
    assert R.layer_rel_err(got, want) > 100 * F32_TOL


def test_layer_checks_pass_and_a_mutation_fails_its_own(params):
    """A prompt's block and a tick's slots (at positions under, at and past
    the window of two pages, through a ring of three) against the
    reference's one attention; the expert layer beside them."""
    cfg = tiny()
    kw = dict(n_tokens=64, page=PAGE)
    assert list(R._tick_positions(WINDOW, 64, PAGE)) == [
        3, 7, 8, 15, 16, 25, 31, 63]
    checks = R.layer_checks(cfg, params, 3, **kw)
    assert set(checks) == {"window_attention", "window_tick",
                           "full_attention", "full_tick", "expert_layer"}
    for name, c in checks.items():
        assert c["rel_err"] < F32_TOL, (name, c)
    for mutate, failed in (
            ({"gate": False}, {"window_attention", "window_tick",
                               "full_attention", "full_tick"}),
            ({"window": WINDOW // 2}, {"window_attention", "window_tick"}),
            ({"window": WINDOW + 1}, {"window_attention", "window_tick"}),
            ({"rotate": {"window": True, "full": True}},
             {"full_attention", "full_tick"}),
            ({"norm_eps_sum": 0.5}, {"expert_layer"}),
            ({"held": (1, 4)}, {"expert_layer"}),
            ({"score": "softmax"}, {"expert_layer"}),
            ({"router_dtype": jnp.bfloat16}, {"expert_layer"})):
        off = R.layer_checks(cfg, params, 3, mutate=mutate, **kw)
        assert {n for n, c in off.items()
                if c["rel_err"] > c["tol"]} == failed, (mutate, off)
    # the nearest precision below the stated one fails every check
    narrow = R.layer_checks(cfg, params, 3, round_to=jnp.float8_e4m3fn, **kw)
    for name, c in narrow.items():
        assert c["rel_err"] > c["tol"], (name, c)


def test_the_logits_reading_leaves_flipped_tokens_out_and_nothing_else():
    """``rel_err``: a block is read by its largest token after the worst one
    in twelve, on the plain scale; a single token against four times it."""
    rng = np.random.default_rng(0)
    want = rng.standard_normal((240, 500)).astype(np.float32)
    scale = 5.5 * float(np.sqrt((want * want).mean()))
    got = want + 0.007 * scale * np.sign(rng.standard_normal(want.shape))
    assert R.rel_err(got, want) == pytest.approx(0.007, rel=1e-3)
    flipped = got.copy()
    flipped[::13] += 0.17 * scale           # 19 of 240: under one in twelve
    assert R.rel_err(flipped, want) == pytest.approx(0.007, rel=1e-3)
    flipped[1::13] += 0.17 * scale          # 38 of 240: over it
    assert R.rel_err(flipped, want) > 0.15
    one = 5.5 * float(np.sqrt((want[0] * want[0]).mean()))
    assert R.rel_err(want[0] + 0.17 * one, want[0]) == pytest.approx(
        0.17 / 5, rel=1e-3)
    assert R.rel_err(want[0] + 0.3 * one, want[0]) > 0.05


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """The share tied to the model: 32 experts held 2 a chip over 16 chips.
    Each share's expert layer as the SYSTEM runs it (its routed part + the
    shared expert), summed with the shared expert counted once, is the uncut
    reference's whole layer."""
    whole = tiny(num_experts=32, moe_experts_held=None)
    params = _seeded(whole, seed=2)
    group, (g_whole, n) = "full_moe", T.layer_groups(whole)["full_moe"]
    lp = {k: v[n - 1] for k, v in params["layers"][group].items()}
    m = jnp.asarray(np.random.default_rng(4).standard_normal((1, 40, 64)),
                    jnp.float32)
    want = R.expert_layer(R.spec(whole), R._layer(params, group, n - 1), m[0])
    shared = T._dense_mlp(g_whole, lp, m, prefix="shared_")[0]
    total = -15 * shared        # every share computes it: counted once
    for chip in range(16):
        g = T.get_config(g_whole, moe_experts_held=2,
                         moe_expert_first=2 * chip)
        mine = {k: (v[2 * chip:2 * chip + 2] if k in T._EXPERT_LEAVES else v)
                for k, v in lp.items()}
        part, _, counts = T._mlp(g, mine, m, jax.random.PRNGKey(0), True)
        assert counts.shape == (2,)
        total = total + part[0]
    assert R.layer_rel_err(total, want) < F32_TOL
    # the reference's own shares add up alike, and one share is not the whole
    s = R.spec(whole)
    parts = sum(R.routed_experts({**s, "held": (2 * c, 2)}, {
        **R._layer(params, group, n - 1),
        **{k: lp[k][2 * c:2 * c + 2] for k in ("w_gate", "w_up", "w_down")}},
        m[0]) for c in range(16))
    assert R.layer_rel_err(parts + shared, want) < F32_TOL
    assert R.layer_rel_err(part[0], want) > 0.1


@pytest.mark.parametrize("window", [8, 12, 20, 100])
@pytest.mark.parametrize("sink", [False, True])
def test_a_window_longer_than_a_chunk_is_walked_inside_itself(monkeypatch,
                                                              window, sink):
    """The walk of a block's own keys in chunks, bounded below by the
    window, against the masked product; the host's copy of its trip counts
    (``causal_walk_steps``, ``block_read_rows``) by hand."""
    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", 4)
    cfg = get_config("tiny", num_heads=4, num_kv_heads=2, head_dim=8,
                     position="none", dtype=jnp.float32)
    rng = np.random.default_rng(0)
    B, S = 2, 32
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, h, 8)), jnp.float32)
               for h in (4, 2, 2))
    b = jnp.asarray(rng.standard_normal((4,)), jnp.float32) if sink else None
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    want = T._attention(cfg, q, k, v, pos, "xla", custom_positions=True,
                        window=window, sink=b)
    got = T._attention_window_block(cfg, q, k, v, pos, window, b)
    np.testing.assert_allclose(got, want, atol=2e-6)
    part = T._attention_window_block(cfg, q, k, v, pos, window, b,
                                     reach=jnp.int32(18))
    np.testing.assert_allclose(part[:, :18], want[:, :18], atol=2e-6)
    assert float(jnp.abs(part[:, 20:]).max()) == 0.0
    # chunk i of 4 queries walks the chunks from its first query's oldest
    # key's to its own
    steps = sum(i + 1 - max(4 * i - window + 1, 0) // 4 for i in range(5))
    assert T.causal_walk_steps(S, 18, window) == steps
    assert T.block_read_rows(S, window, 18) == 4 * steps
    assert T.causal_walk_steps(S, 18) == 15 >= steps


def test_engine_serves_through_a_ring_of_several_pages(engine):
    """Prompts under, at and past the window through three slots, every
    slot taken again: each yields what greedy ``forward`` yields; the rings
    (3 pages a slot) come back; one decode program."""
    cfg = engine.model.config
    reqs = _requests([3, 16, 41, 9, 30, 17, 55], seed=1)
    sv = engine.serving(**SERVE_KW)
    lay = sv._exec.layout
    assert (lay.kind, lay.ring_pages, lay.window_pages) == ("window", 3, 10)
    assert set(sv.params["layers"]) == {"window_dense", "window_moe",
                                        "full_moe"}
    assert sv._exec._pool_keys == ("k", "v", "k_window", "v_window")
    assert sv._prefix is None
    info = sv._exec.mesh_info()
    assert (info["cache_kind"], info["kv_layers"], info["ring_pages"]) == (
        "window", 8, 3)
    assert set(info["kv_read"]) == {"k", "v", "k_window", "v_window"}
    assert sv._exec.moe_shape == (7, 4)
    results = {r.rid: r for r in sv.run(reqs, max_ticks=4000)}
    for q in reqs:
        out = results[q.rid].output_ids
        assert len(out) == q.max_new_tokens
        assert _is_greedy(cfg, engine.params, q.input_ids, out), q.rid
    acct = sv.page_accounting()
    assert acct["balanced"] and acct["window"] == {
        "free": 9, "quarantined": 0, "referenced": 0, "total": 9,
        "balanced": True}
    assert sv._exec._decode_prog._cache_size() == 1
    assert sv.health()["lookahead_dropped_total"] == 0


def test_spans_carry_both_pools_rows_and_the_slots_past_the_window(
        engine, monkeypatch):
    from deepspeed_tpu.observability import Span, configure_tracer, get_tracer

    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", 4)     # tiny prompts walk
    sv = engine.serving(**SERVE_KW)
    get_tracer().reset()
    configure_tracer(enabled=True)
    try:
        sv.run(_requests([3, 16, 41, 9, 30], seed=2), max_ticks=4000)
        spans = [s for s in get_tracer().recorder.snapshot()
                 if isinstance(s, Span)]
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    decode = [s.attrs for s in spans if s.name == "serve.decode"]
    prefill = [s.attrs for s in spans if s.name == "serve.prefill"]
    assert decode and len(prefill) == 5
    # 2 full layers x 2 KV heads, 6 window layers x 2: what a token row is
    for a in decode:
        assert 1 <= a["kv_slots_live"] <= 3
        assert 0 <= a["kv_slots_past_window"] <= a["kv_slots_live"]
        assert a["kv_rows_full"] == a["gathered_rows"] * 4
        assert a["kv_live_rows_full"] == 4 * (a["live_rows"]
                                              + a["kv_slots_live"])
        assert a["kv_live_rows_window"] <= a["kv_slots_live"] * WINDOW * 12
        # a slot under the window holds the same rows in both kinds
        if a["kv_slots_past_window"] == 0:
            assert a["kv_live_rows_window"] == 3 * a["kv_live_rows_full"]
        else:
            assert a["kv_live_rows_window"] < 3 * a["kv_live_rows_full"]
        assert 0 < a["kv_live_rows_window"] <= a["kv_rows_window"]
        assert a["moe_experts_held"] == 7 * 4
        assert a["moe_pairs"] == a["kv_slots_live"] * 3 * 7
        assert a["moe_local_pairs"] == a["moe_rows"] <= a["moe_pairs"]
    assert any(a["kv_slots_past_window"] for a in decode)
    assert any(a["kv_slots_past_window"] < a["kv_slots_live"] for a in decode)
    for a in prefill:
        assert a["gathered_rows"] == 0
        assert a["kv_rows_full"] == 4 * T.block_read_rows(
            a["bucket"], tokens=a["tokens"])
        assert a["kv_rows_window"] == 12 * T.block_read_rows(
            a["bucket"], WINDOW, tokens=a["tokens"])
        assert a["kv_rows_window"] <= 3 * a["kv_rows_full"]
        assert a["pairs_held"] <= a["pairs_total"] == a["tokens"] * 3 * 7
    # 4 of 16 experts held: about a quarter of the pairs land here
    share = (sum(a["moe_local_pairs"] for a in prefill)
             / sum(a["moe_pairs"] for a in prefill))
    assert 0.1 < share < 0.45


def test_the_gate_has_its_scope_in_every_program(params):
    cfg = tiny()
    toks = jnp.zeros((1, 8), jnp.int32)
    text = jax.jit(lambda p, t: T.forward(cfg, p, t)).lower(
        params, toks).as_text(debug_info=True)
    assert "attn_gate" in text
    model = CausalLM(cfg)
    cache = model.init_paged_cache(4, PAGE)
    tick = jax.jit(model.apply_paged).lower(
        params, toks[:, :1], cache, jnp.ones((1, 3), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.ones((1, 1), bool)
    ).as_text(debug_info=True)
    assert "attn_gate" in tick


# what refuses a model of two kinds of layer refuses this one: MiMo's table
# (the same engine geometry), each call on this model's engine
@pytest.mark.parametrize("what", list(REFUSALS))
def test_mechanisms_that_assume_one_pool_refuse_by_name(engine, what):
    named, call = REFUSALS[what]
    with pytest.raises(NotImplementedError, match="window") as e:
        call(engine)
    assert named in str(e.value)


def test_tensor_sharded_heads_refuse():
    from deepspeed_tpu.parallel.mesh import initialize_serving_mesh

    cfg = tiny()
    mesh = initialize_serving_mesh(tp=2)
    with pytest.raises(NotImplementedError, match="tensor-sharded heads"):
        MeshExecutor(CausalLM(cfg), jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0))), 13, 8, 3,
            mesh=mesh, prefix_cache=False)


def test_a_uniform_model_carries_the_gate_and_an_adapter_on_it():
    """The gate is a field of any model: a uniform stack with it matches
    the same stack with the gate's projection at 0 times one half; and a
    LoRA configuration may name ``wg`` like any projection."""
    from deepspeed_tpu.runtime.lora import LoRAConfig, init_lora_params

    cfg = get_config("tiny", attn_output_gate=True, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert params["layers"]["wg"].shape == (2, 64, 64)
    assert cfg.param_count == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    toks = _tokens(12)
    gated = T.forward(cfg, params, toks)
    # sigmoid(0) = 1/2: the gate at rest halves attention's output
    rest = {**params, "layers": {**params["layers"],
                                 "wg": 0 * params["layers"]["wg"],
                                 "wo": 0.5 * params["layers"]["wo"]}}
    plain = T.forward(get_config(cfg, attn_output_gate=False),
                      {**rest, "layers": {k: v for k, v in
                                          rest["layers"].items()
                                          if k != "wg"}}, toks)
    np.testing.assert_allclose(
        T.forward(cfg, {**rest, "layers": {
            **rest["layers"], "wo": params["layers"]["wo"]}}, toks),
        plain, atol=1e-5)
    assert float(jnp.abs(gated - plain).max()) > 1e-4
    lora = init_lora_params(params["layers"],
                            LoRAConfig(rank=2, targets=("wq", "wg")),
                            jax.random.PRNGKey(1))
    assert set(lora) == {"wq", "wg"}
