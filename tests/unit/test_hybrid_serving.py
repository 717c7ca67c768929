"""A model with window and full attention layers (``layer_pattern``, MiMo-V2)
through the serving engine: the two pools and their allocator, the ring's
plan against the host's formula, the span attrs, and the mechanisms that
refuse such a model by name."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.execution import MeshExecutor
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.resilience import (FaultInjector, SITE_SERVE_PREFILL,
                                      clear_injector, install_injector)

SERVE_KW = dict(b_slots=3, page_size=8, max_model_len=96)


def tiny(**over):
    kw = dict(num_layers=7, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
              window_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
              window_size=16, num_experts=16, moe_experts_held=4, moe_top_k=3,
              vocab_size=256, max_seq_len=512, dtype=jnp.float32)
    kw.update(over)
    return get_config("mimo-v2.5", **kw)


@pytest.fixture(autouse=True)
def _clean_injector():
    clear_injector()
    yield
    clear_injector()


@pytest.fixture(scope="module")
def engine():
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    cfg = tiny()
    model = CausalLM(cfg)
    # one device, as the benchmark's cell: the uncached forward it is
    # compared with takes a batch of one
    return deepspeed_tpu.init_inference(
        model=model, params=init_params(cfg, jax.random.PRNGKey(0)),
        dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))


def _requests(n, seed=0, lo=3, hi=40, new=(6, 30)):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", arrival_time=0.0,
                    max_new_tokens=int(rng.integers(*new)),
                    input_ids=rng.integers(0, 256, (int(rng.integers(lo, hi)),)
                                           ).astype(np.int32))
            for i in range(n)]


def test_params_and_pools_are_grouped_by_kind():
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert list(params["layers"]) == ["full_dense", "window_moe", "full_moe"]
    assert [k for _, _, k, _ in T.layer_plan(cfg)] == [
        "full", "window", "window", "window", "window", "full", "window"]
    w, f = params["layers"]["window_moe"], params["layers"]["full_moe"]
    assert w["wk"].shape == (5, 64, 4 * 24) and f["wk"].shape == (1, 64, 2 * 24)
    assert w["wv"].shape == (5, 64, 4 * 16) and w["attn_sink"].shape == (5, 8)
    assert "attn_sink" not in f and "router" not in params["layers"]["full_dense"]
    assert w["router"].shape == (5, 64, 16) and w["w_gate"].shape == (5, 4, 64, 32)
    assert params["layers"]["full_dense"]["w_gate"].shape == (1, 64, 96)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == cfg.param_count
    assert jax.tree_util.tree_structure(T.param_specs(cfg)) == \
        jax.tree_util.tree_structure(params)
    cache = T.init_paged_cache(cfg, 9, 8, window_pages=7)
    assert cache["k"].shape == (2, 9, 8, 2, 24)
    assert cache["v"].shape == (2, 9, 8, 2, 16)
    assert cache["k_window"].shape == (5, 7, 8, 4, 24)
    assert cache["v_window"].shape == (5, 7, 8, 4, 16)
    # the published model: 309 B parameters, 9 full layers of 48
    full = get_config("mimo-v2.5")
    assert round(full.param_count / 1e9, 1) == 308.8
    assert sum(k == "full" for _, _, k, _ in T.layer_plan(full)) == 9


def _sparse_prompts():
    """Prompts that leave more than half of their bucket's chunks of 4
    empty (3 of 16 tokens: one chunk of four) and about half (17 of 32; 33
    of 64, which leaves a window layer's last chunk of 16 empty too)."""
    rng = np.random.default_rng(11)
    return [Request(rid=f"sparse{n}", arrival_time=0.0, max_new_tokens=24,
                    input_ids=rng.integers(0, 256, (n,)).astype(np.int32))
            for n in (3, 17, 33)]


@pytest.mark.parametrize("chunk", [512, 4])
def test_engine_serves_both_pools_and_gives_the_rings_back(engine,
                                                           monkeypatch, chunk):
    """``chunk`` 4: every prompt's full layers walk their chunks, as far as
    the prompt's own tokens reach (512, as shipped: the masked product)."""
    from deepspeed_tpu.models.transformer import forward

    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", chunk)
    if chunk == 4:      # and its window layers their chunks of 16 one by one
        monkeypatch.setattr(T, "WINDOW_BLOCK_CHUNKS", 1)
    sv = engine.serving(**SERVE_KW)
    lay = sv._exec.layout
    assert lay.ring_pages == 3 and lay.window_pages == 1 + 3 * 3
    assert sv._prefix is None                       # off for this model
    reqs = _requests(7) + _sparse_prompts()
    results = sv.run(reqs, max_ticks=4000)
    assert len(results) == 10
    cfg, params = engine.model.config, engine.params
    # greedy, token for token, past the ring
    for r in results[:3] + results[-3:]:
        ids = np.concatenate([r.input_ids, r.output_ids])
        assert len(ids) > 16 + 8
        greedy = np.asarray(jnp.argmax(jax.jit(
            lambda p, t: forward(cfg, p, t))(params, jnp.asarray(ids)[None]),
            -1))[0]
        n = len(r.input_ids)
        assert (greedy[n - 1:-1] == r.output_ids).all()
    acct = sv.page_accounting()
    assert acct["balanced"] and acct["window"] == {
        "free": 9, "quarantined": 0, "referenced": 0, "total": 9,
        "balanced": True}
    assert sv._exec._decode_prog._cache_size() == 1
    assert sv.health()["lookahead_dropped_total"] == 0


def test_accounting_after_admit_wrap_and_finish(engine):
    sv = engine.serving(**SERVE_KW)
    long = Request(rid="long", arrival_time=0.0, max_new_tokens=60,
                   input_ids=np.arange(5, dtype=np.int32))
    short = Request(rid="short", arrival_time=0.0, max_new_tokens=3,
                    input_ids=np.arange(9, dtype=np.int32))
    sv.submit(long), sv.submit(short)
    sv.step(now=0.0)
    acct = sv.page_accounting()
    assert acct["balanced"] and acct["window"]["referenced"] == 6
    ring = sv._pools[1].table[0].copy()
    assert ring.all() and len(set(ring) | set(sv._pools[1].table[1])) == 6
    while sv._active[1]:
        sv.step()
    acct = sv.page_accounting()
    assert acct["balanced"] and acct["window"]["referenced"] == 3
    assert acct["window"]["free"] == 6 and not sv._pools[1].table[1].any()
    while sv._lengths[0] < 5 + 40:          # 45 positions: the ring of 3
        sv.step()                           # pages has wrapped, in place
    assert (sv._pools[1].table[0] == ring).all()
    assert sv.page_accounting()["balanced"]
    while sv._active.any():
        sv.step()
    acct = sv.page_accounting()
    assert acct["balanced"] and acct["window"]["free"] == 9
    assert acct["free"] == acct["total"]


def test_a_failed_prefill_returns_its_ring_and_a_fenced_slot_keeps_it(engine):
    sup = engine.supervised_serving(**SERVE_KW)
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", at_call=1)
    (res,) = sup.run(_requests(1, seed=3), max_ticks=2000)
    assert res.finish_reason == "length"
    acct = sup.engine.page_accounting()
    assert acct["balanced"] and acct["window"]["free"] == 9
    # two in a row fence the slot: its ring is leaked with its pages
    clear_injector()
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", every=1, max_fires=2)
    results = sup.run(_requests(3, seed=4), max_ticks=4000)
    assert all(r.finish_reason == "length" for r in results)
    eng = sup.engine
    assert bool(eng._quarantined[0])
    acct = eng.page_accounting()
    assert acct["balanced"]
    assert acct["window"] == {"free": 6, "quarantined": 3, "referenced": 0,
                              "total": 9, "balanced": True}


@pytest.mark.parametrize("page,window", [(8, 16), (16, 16), (4, 6)])
def test_ring_read_plan_is_the_hosts_formula(page, window):
    """``window_read_rows`` (the ``kv_rows_window`` span attr) is the trip
    count of the plan the program computes on the device, and the plan's
    pairs are exactly the pages under each live slot's last ``window``
    positions."""
    B = 4
    R = T.window_ring_pages(window, page)
    table = jnp.arange(1, 1 + B * R, dtype=jnp.int32).reshape(B, R)
    rng = np.random.default_rng(page + window)
    for _ in range(6):
        lengths = rng.integers(0, 5 * page, B)
        active = rng.random(B) < 0.7
        steps, slot, pages, limit, low = T._ring_read_plan(
            table, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(active)[:, None], page, window)
        pairs = T.paged_read_pairs(B, R)
        rows = T.window_read_rows((lengths + 1) * active, page, window, B)
        assert int(steps) * pairs * page == rows
        slot, pages = np.asarray(slot).ravel(), np.asarray(pages).ravel()
        limit, low = np.asarray(limit).reshape(-1), np.asarray(low).reshape(-1)
        for b in range(B):
            mine = slot == b
            if not active[b]:
                assert not mine.any()
                continue
            pos = lengths[b]
            want = list(range(max(pos - window + 1, 0) // page,
                              pos // page + 1))
            assert list(pages[mine]) == [int(table[b, j % R]) for j in want]
            seen = sorted(
                j * page + r for j, lim, lo in zip(want, limit[mine],
                                                   low[mine])
                for r in range(page) if lo <= r <= lim)
            assert seen == list(range(max(pos - window + 1, 0), pos + 1))
        assert not pages[slot >= B].any()


def test_spans_carry_the_rows_of_each_kind_and_the_held_pairs(engine,
                                                              monkeypatch):
    from deepspeed_tpu.observability import Span, configure_tracer, get_tracer

    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", 4)
    monkeypatch.setattr(T, "WINDOW_BLOCK_CHUNKS", 1)
    sv = engine.serving(**SERVE_KW)
    sv.run(_requests(2, seed=1), max_ticks=2000)        # warm
    get_tracer().reset()     # spans an earlier test of this process left
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
    ticks = [s.attrs for s in spans if s.name == "serve.tick"
             and "pages_full" in (s.attrs or {})]
    assert decode and prefill and ticks
    for a in decode + prefill:
        assert 0 < a["kv_live_rows_full"] <= a["kv_rows_full"]
        assert 0 < a["kv_live_rows_window"] <= a["kv_rows_window"]
        assert a["moe_experts_held"] == 6 * 4
        assert a["moe_local_pairs"] == a["moe_rows"] <= a["moe_pairs"]
        assert a["moe_experts_touched"] <= a["moe_experts_held"]
    # 2 full layers x 2 KV heads, 5 window layers x 4: what a token row is
    for a in decode:
        assert a["kv_rows_full"] == a["gathered_rows"] * 4
        assert a["kv_rows_window"] % 20 == 0
        assert a["kv_live_rows_window"] <= 3 * 16 * 20
    # a prompt's full layers walk chunks of 4 as far as its own tokens
    # reach into the bucket; its window layers two chunks of 16 a chunk that
    # holds a token (taken one at a time here)
    for a in prefill:
        r, n = -(-a["tokens"] // 4), a["bucket"] // 4
        assert a["walk_steps"] == r * (r + 1) // 2
        assert a["walk_steps_bucket"] == n * (n + 1) // 2
        assert a["kv_rows_full"] == 4 * a["walk_steps"] * 4
        assert a["kv_rows_full"] == 4 * T.block_read_rows(
            a["bucket"], tokens=a["tokens"])
        assert a["kv_rows_window"] == 20 * T.block_read_rows(
            a["bucket"], 16, tokens=a["tokens"])
    assert (sum(a["walk_steps"] for a in prefill)
            < sum(a["walk_steps_bucket"] for a in prefill))
    # 4 of 16 experts held: about a quarter of the pairs land here
    share = (sum(a["moe_local_pairs"] for a in prefill)
             / sum(a["moe_pairs"] for a in prefill))
    assert 0.1 < share < 0.45
    assert all(0 < a["pages_window"] <= 9 and a["pages_window"] % 3 == 0
               for a in ticks)


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
