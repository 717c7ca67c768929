"""A model with latent attention (``kv_lora_rank``, Kanana-2 /
``deepseek_v3``), a leading dense layer and shared experts beside a held
share of routed ones, through the serving engine: the one latent cache leaf,
the expanded and the absorbed path, the pair list's row count against the
device's trip count, the span attrs, and the mechanisms that refuse such a
model by name."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.execution import MeshExecutor
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T

SERVE_KW = dict(b_slots=3, page_size=8, max_model_len=96)


def tiny(**over):
    kw = dict(num_layers=4, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_heads=4, head_dim=24,
              v_head_dim=16, rotary_dim=8, kv_lora_rank=32, num_experts=16,
              moe_experts_held=4, moe_top_k=3, vocab_size=256,
              max_seq_len=512, dtype=jnp.float32)
    kw.update(over)
    return get_config("kanana-2-30b-a3b", **kw)


@pytest.fixture(scope="module")
def engine():
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    cfg = tiny()
    return deepspeed_tpu.init_inference(
        model=CausalLM(cfg), params=init_params(cfg, jax.random.PRNGKey(0)),
        dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))


def _requests(n, seed=0, lo=3, hi=40, new=(6, 30)):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", arrival_time=0.0,
                    max_new_tokens=int(rng.integers(*new)),
                    input_ids=rng.integers(0, 256, (int(rng.integers(lo, hi)),)
                                           ).astype(np.int32))
            for i in range(n)]


def test_params_are_grouped_and_the_pool_is_one_latent_leaf():
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert list(params["layers"]) == ["full_dense", "full_moe"]
    d, m = params["layers"]["full_dense"], params["layers"]["full_moe"]
    for g, n in ((d, 1), (m, 3)):
        assert "wk" not in g and "wv" not in g
        assert g["wq"].shape == (n, 64, 4 * 24)
        assert g["wkv_a"].shape == (n, 64, 32 + 8)
        assert g["kv_a_norm_scale"].shape == (n, 32)
        assert g["wkv_b"].shape == (n, 32, 4 * (16 + 16))
        assert g["wo"].shape == (n, 4 * 16, 64)
    assert d["w_gate"].shape == (1, 64, 96) and "router" not in d
    assert "shared_w_gate" not in d
    assert m["router"].shape == (3, 64, 16) and m["router_bias"].shape == (3, 16)
    assert m["w_gate"].shape == (3, 4, 64, 32)           # 4 of 16 held
    assert m["shared_w_gate"].shape == (3, 64, 2 * 32)   # one MLP of 2 widths
    assert m["shared_w_down"].shape == (3, 2 * 32, 64)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == cfg.param_count
    assert jax.tree_util.tree_structure(T.param_specs(cfg)) == \
        jax.tree_util.tree_structure(params)
    cache = T.init_paged_cache(cfg, 9, 8)
    assert list(cache) == ["latent"] and cache["latent"].shape == (4, 9, 8, 40)
    assert T.paged_pool_tuple(cache)[0] is cache["latent"]
    # every share of 4 experts holds the same biases, under any seed
    bias = np.sort(np.asarray(m["router_bias"]).reshape(3, 4, 4), axis=-1)
    assert np.allclose(bias, bias[0, 0])
    # the published model: 48 layers, 128 experts, about 30 B parameters
    full = get_config("kanana-2-30b-a3b")
    assert 29.5 < full.param_count / 1e9 < 31.5
    cut = get_config("kanana-2-30b-a3b", num_layers=24, moe_experts_held=16,
                     vocab_size=16032)
    assert round(cut.param_count / 1e9, 2) == 2.70


def test_absorbed_is_expanded_to_rounding():
    """One layer's attention both ways in float32: the expanded keys and
    values through the masked product, and the same rows read back from the
    pool through the absorbed path, a token at a time."""
    cfg = tiny()
    g = T.layer_groups(cfg)["full_moe"][0]
    lp = jax.tree_util.tree_map(
        lambda a: a[1], init_params(cfg, jax.random.PRNGKey(3))["layers"]
        ["full_moe"])
    rng = np.random.default_rng(0)
    n, ps = 29, 8
    h = jnp.asarray(rng.normal(size=(1, n, 64)), jnp.float32)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    q, latent = T._qkv_latent(g, lp, h, pos)
    k, v = T._latent_expand(g, latent, lp["wkv_b"])
    want = T._attention(g, q, k, v, pos)
    pool = jnp.zeros((1 + 4, ps, 40), jnp.float32)
    table = jnp.arange(1, 5, dtype=jnp.int32)[None]
    worst = 0.0
    for t in range(n):
        start = jnp.full((1,), t, jnp.int32)
        mask = jnp.ones((1, 1), bool)
        attend = T._attend_latent_paged(
            g, {"latent": pool}, T._paged_write_plan(table, start, mask, ps),
            T._paged_read_plan(table, start, mask, ps))
        out, new = attend(q[:, t:t + 1], latent[:, t:t + 1], lp["wkv_b"])
        pool = new["latent"]
        worst = max(worst, float(jnp.abs(out[0, 0] - want[0, t]).max()))
    assert worst < 2e-6 * float(jnp.abs(want).max()) + 1e-7, worst
    # the cache row is [the normed latent ; the shared rotated key row]
    assert np.allclose(np.asarray(pool[1:]).reshape(-1, 40)[:n], latent[0])


def _sparse_prompts():
    """Prompts that leave more than half of their bucket's chunks of 4
    empty (3 of 16 tokens: one chunk of four) and about half (17 of 32)."""
    rng = np.random.default_rng(11)
    return [Request(rid=f"sparse{n}", arrival_time=0.0, max_new_tokens=9,
                    input_ids=rng.integers(0, 256, (n,)).astype(np.int32))
            for n in (3, 17)]


@pytest.mark.parametrize("chunk", [512, 4])
def test_engine_serves_the_latent_pool_token_for_token(engine, monkeypatch,
                                                       chunk):
    """``chunk`` 4: every prompt here walks its chunks, as far as its own
    tokens reach (512, as shipped: they take the masked product)."""
    from deepspeed_tpu.models.transformer import forward

    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", chunk)
    sv = engine.serving(**SERVE_KW)
    assert sv._prefix is None and sv._layout.kind == "latent"  # sharing off
    assert sv._exec._pool_keys == ("latent",)
    assert sv._exec.moe_shape == (3, 4)
    results = sv.run(_requests(7) + _sparse_prompts(), max_ticks=4000)
    assert len(results) == 9
    cfg, params = engine.model.config, engine.params
    for r in results[:3] + results[-2:]:
        ids = np.concatenate([r.input_ids, r.output_ids])
        greedy = np.asarray(jnp.argmax(jax.jit(
            lambda p, t: forward(cfg, p, t))(params, jnp.asarray(ids)[None]),
            -1))[0]
        n = len(r.input_ids)
        assert (greedy[n - 1:-1] == r.output_ids).all()
    acct = sv.page_accounting()
    assert acct["balanced"] and acct["free"] == acct["total"]
    assert sv._exec._decode_prog._cache_size() == 1
    assert sv.health()["lookahead_dropped_total"] == 0
    # the plain loop emits the same tokens
    plain = engine.serving(lookahead=False, **SERVE_KW).run(
        _requests(7) + _sparse_prompts(), max_ticks=4000)
    assert all((a.output_ids == b.output_ids).all()
               for a, b in zip(results, plain))


@pytest.mark.parametrize("page", [8, 16])
def test_latent_rows_read_are_the_devices_trip_count(page):
    """``paged_read_rows`` (the ``gathered_rows`` span attr) is the trip
    count of the pair list the program computes on the device and hands the
    absorbed read, under both paths, and every live row is on the list
    once."""
    B, maxp = 4, 6
    table = jnp.arange(1, 1 + B * maxp, dtype=jnp.int32).reshape(B, maxp)
    rng = np.random.default_rng(page)
    for _ in range(6):
        lengths = rng.integers(0, maxp * page - 1, B)
        active = rng.random(B) < 0.7
        steps, slot, pages, limit = T._paged_read_plan(
            table, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(active)[:, None], page)
        rows = T.paged_read_rows((lengths + 1) * active, page, maxp, B)
        assert int(steps) * T.paged_read_pairs(B, maxp) * page == rows
        # ... in whole steps where the read gathers; the kernel's grid is as
        # long as the live pairs (``kv_read_path``'s ``"pages"``)
        assert int((np.asarray(slot) < B).sum()) * page == T.paged_read_rows(
            (lengths + 1) * active, page, maxp, B, whole_steps=False) <= rows
        live = int(((np.asarray(limit)[..., 0].ravel()[:, None]
                     >= np.arange(page)[None]).sum()))
        assert live == int(((lengths + 1) * active).sum()) <= rows


@pytest.mark.parametrize("block,chunk", [(64, 16), (32, 4), (16, 4)])
def test_a_prompts_rows_read_are_the_walks_trip_count(monkeypatch, block,
                                                      chunk):
    """``block_read_rows(block, tokens=n)`` (and the ``walk_steps`` span
    attr) is what the walk runs for the mask the prefill program builds:
    query chunk ``i`` of the ``r`` that real tokens reach runs ``i + 1``
    steps of ``chunk`` keys, a chunk past them none."""
    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", chunk)
    for n in range(1, block + 1):
        seq_mask = (jnp.arange(block, dtype=jnp.int32) < n)[None, :]
        assert int(T._block_reach(seq_mask)) == n
        r = -(-n // chunk)          # query chunk i runs where i * chunk < n
        steps = r * (r + 1) // 2
        assert T.causal_walk_steps(block, n) == steps
        assert T.block_read_rows(block, tokens=n) == chunk * steps
    assert T.block_read_rows(block) == T.block_read_rows(block, tokens=block)
    # a block under two chunks is one masked product over all of itself
    assert T.causal_walk_steps(chunk, 1) == 1
    assert T.block_read_rows(chunk, tokens=1) == chunk


def test_spans_carry_the_latent_rows_and_the_held_pairs(engine, monkeypatch):
    from deepspeed_tpu.observability import Span, configure_tracer, get_tracer

    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", 4)
    sv = engine.serving(**SERVE_KW)
    sv.run(_requests(2, seed=1), max_ticks=2000)        # warm
    configure_tracer(enabled=True)
    try:
        sv.run(_requests(5, seed=2), max_ticks=4000)
        spans = [s for s in get_tracer().recorder.snapshot()
                 if isinstance(s, Span)]
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    decode = [s.attrs for s in spans if s.name == "serve.decode"]
    prefill = [s.attrs for s in spans if s.name == "serve.prefill"]
    assert decode and prefill
    for a in decode:
        assert 0 < a["live_rows"] <= a["gathered_rows"]
        assert a["gathered_rows"] % (T.paged_read_pairs(3, 12) * 8) == 0
    for a in prefill:       # a prompt attends within itself: nothing read
        assert a["gathered_rows"] == 0
        # ... in chunks of 4, as far as its own tokens reach into the bucket
        r, n = -(-a["tokens"] // 4), a["bucket"] // 4
        assert a["walk_steps"] == r * (r + 1) // 2
        assert a["walk_steps_bucket"] == n * (n + 1) // 2
    assert (sum(a["walk_steps"] for a in prefill)
            < sum(a["walk_steps_bucket"] for a in prefill))
    for a in decode + prefill:
        assert a["moe_experts_held"] == 3 * 4
        assert a["moe_local_pairs"] == a["moe_rows"] <= a["moe_pairs"]
        assert a["moe_experts_touched"] <= a["moe_experts_held"]
    share = (sum(a["moe_local_pairs"] for a in prefill)
             / sum(a["moe_pairs"] for a in prefill))
    assert 0.1 < share < 0.45      # 4 of 16 held


REFUSALS = {
    "prefix sharing": ("prefix sharing", lambda e: e.serving(
        prefix_cache=True, **SERVE_KW)),
    "tiering": ("KV-page tiering", lambda e: e.serving(
        host_tier_pages=4, **SERVE_KW)),
    "speculative": ("speculative decoding", lambda e: e.serving(
        speculative=object(), **SERVE_KW)),
    "int8 pool": ("int8 pool", lambda e: e.serving(
        kv_dtype="int8", **SERVE_KW)),
    "copy-on-write": ("copy-on-write", lambda e: MeshExecutor(
        e.model, e.params, 13, 8, 3, prefix_cache=True)),
    "adapters": ("adapter", lambda e: T.forward_paged(
        e.model.config, e.params, jnp.zeros((1, 1), jnp.int32),
        e.model.init_paged_cache(4, 8), jnp.ones((1, 3), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.ones((1, 1), bool),
        adapters={"scale": jnp.ones((1,)), "factors": {}})),
    "adapter registry": ("multi-tenant adapters", lambda e: MeshExecutor(
        e.model, e.params, 13, 8, 3, prefix_cache=False, adapters=object())),
    "contiguous cache": ("contiguous cache", lambda e: e.generate(
        np.arange(4, dtype=np.int32)[None], max_new_tokens=2)),
    "training": ("training", lambda e: T.forward(
        e.model.config, e.params, jnp.zeros((1, 4), jnp.int32),
        deterministic=False)),
    "flash kernel": ("flash kernel", lambda e: T.forward(
        e.model.config, e.params, jnp.zeros((1, 4), jnp.int32),
        attn_impl="pallas")),
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_mechanisms_that_assume_k_and_v_leaves_refuse_by_name(engine, what):
    named, call = REFUSALS[what]
    with pytest.raises(NotImplementedError, match="latent") as e:
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


def test_a_model_that_only_leads_with_dense_layers_names_itself():
    """Grouped stacks without latent attention (a leading dense layer before
    expert layers, K and V heads): the paged path runs it, and what assumes
    one stack says which model it was handed."""
    cfg = get_config("olmoe-1b-7b", num_layers=3, hidden_size=64,
                     intermediate_size=32, num_heads=4, vocab_size=256,
                     num_experts=8, moe_top_k=3, dense_layers=1,
                     dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert list(params["layers"]) == ["full_dense", "full_moe"]
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (1, 21)),
                       jnp.int32)
    want = T.forward(cfg, params, toks)
    cache = T.init_paged_cache(cfg, 5, 8, dtype=jnp.float32)
    table = jnp.arange(1, 5, dtype=jnp.int32)[None]
    pad = jnp.zeros((1, 24), jnp.int32).at[:, :21].set(toks)
    got, _, counts = T.forward_paged(
        cfg, params, pad, cache, table, jnp.zeros((1,), jnp.int32),
        (jnp.arange(24) < 21)[None], expert_counts=True)
    assert counts.shape == (2, 8) and int(counts.sum()) == 2 * 21 * 3
    assert float(jnp.abs(got[0, :21] - want[0]).max()) < 1e-5
    with pytest.raises(NotImplementedError, match="leading dense layers"):
        T.forward(cfg, params, toks, deterministic=False)
