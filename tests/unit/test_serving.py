"""Continuous-batching serving engine tests (ISSUE 2 tentpole).

Covers: ragged-stream token parity vs per-request ``generate()``, the
constant program inventory (zero-recompile admission), paged-pool
bookkeeping, EOS/length retirement, arrival gating, monitor gauges, and the
chaos-marker admission-under-delay case.

Compile discipline (single-core CI): one module-scoped tiny engine + ONE
shared ServingEngine shape serve most tests, and streams draw max_new from
a small choice set — every distinct (bucket, max_new) pair costs a baseline
generate() scan compile.
"""
import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.monitor import InMemoryMonitor
from deepspeed_tpu.resilience import (FaultInjector, SITE_SERVE_ADMIT,
                                      SITE_SERVE_TICK, clear_injector,
                                      install_injector)

from deepspeed_tpu.utils.compile_counter import compile_counter

_compile_count = compile_counter()


def _make_engine(model_name="tiny", **overrides):
    model = CausalLM(model_name, dtype=jnp.float32, attn_impl="xla",
                     **overrides)
    params = model.init_fn(jax.random.PRNGKey(3))
    return deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)


@pytest.fixture(scope="module")
def tiny_engine():
    return _make_engine()


@pytest.fixture(scope="module")
def tiny_serve(tiny_engine):
    """One shared slot fleet (multi-page: page_size 8 < prompts+outputs).
    run() drains completely, so tests can safely share it."""
    return tiny_engine.serving(b_slots=3, page_size=8, max_model_len=64,
                               monitor=InMemoryMonitor())


def _stream(n, seed=0, smin=3, smax=14, new_choices=(4, 6, 8), vocab=250,
            eos=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    input_ids=rng.integers(1, vocab,
                                           int(rng.integers(smin, smax))
                                           ).astype(np.int32),
                    max_new_tokens=int(rng.choice(new_choices)),
                    eos_token_id=eos)
            for i in range(n)]


def _assert_parity(engine, results, requests):
    by_rid = {r.rid: r for r in requests}
    assert sorted(r.rid for r in results) == sorted(by_rid)
    for res in results:
        req = by_rid[res.rid]
        base = np.asarray(engine.generate(
            req.input_ids[None], max_new_tokens=req.max_new_tokens,
            eos_token_id=req.eos_token_id))[0, len(req.input_ids):]
        if req.eos_token_id is not None:
            # generate() pads to max_new repeating eos; serving stops at eos
            n = len(res.output_ids)
            np.testing.assert_array_equal(res.output_ids, base[:n])
            if res.finish_reason == "eos":
                assert res.output_ids[-1] == req.eos_token_id
                assert (base[n:] == req.eos_token_id).all()
        else:
            np.testing.assert_array_equal(res.output_ids, base)


@pytest.mark.slow
def test_serving_parity_mixed_length_stream(tiny_engine, tiny_serve):
    """A ragged mixed-length stream through the slot scheduler must be
    token-identical to per-request greedy generate() (acceptance)."""
    reqs = _stream(8, seed=1)
    results = tiny_serve.run(list(reqs))
    _assert_parity(tiny_engine, results, reqs)
    # slots returned; every page is free or pinned by the prefix index
    # (refcount pool invariant — pages linger as cache, never leak)
    assert not tiny_serve._active.any()
    acct = tiny_serve.page_accounting()
    assert acct["balanced"], acct
    assert acct["referenced"] == acct["cached"]   # only the index holds refs


@pytest.mark.slow
def test_serving_parity_gqa():
    """Grouped-query attention through the paged pool."""
    engine = _make_engine("tiny-gqa")
    serve = engine.serving(b_slots=2, page_size=8, max_model_len=64)
    reqs = _stream(3, seed=2, smin=9, smax=14, new_choices=(5, 7))
    results = serve.run(list(reqs))
    _assert_parity(engine, results, reqs)


@pytest.mark.slow
def test_serving_parity_alibi():
    """Position-from-slot-index must hold for alibi's relative biases."""
    engine = _make_engine("tiny", position="alibi", norm="layernorm",
                          activation="gelu")
    serve = engine.serving(b_slots=2, page_size=16, max_model_len=64)
    reqs = _stream(3, seed=3, new_choices=(5, 7))
    results = serve.run(list(reqs))
    _assert_parity(engine, results, reqs)


def test_serving_eos_retires_slot(tiny_engine, tiny_serve):
    probe = _stream(1, seed=4, new_choices=(8,))[0]
    base = np.asarray(tiny_engine.generate(probe.input_ids[None],
                                           max_new_tokens=8))[0]
    eos = int(base[len(probe.input_ids) + 2])   # 3rd generated token
    req = Request(rid="e", input_ids=probe.input_ids, max_new_tokens=8,
                  eos_token_id=eos)
    (res,) = tiny_serve.run([req])
    assert res.finish_reason == "eos"
    assert res.output_ids[-1] == eos
    assert len(res.output_ids) <= 8
    _assert_parity(tiny_engine, [res], [req])
    assert not tiny_serve._active.any()
    assert tiny_serve.page_accounting()["balanced"]


def test_serving_zero_recompile_admission(tiny_engine, tiny_serve):
    """Acceptance: at steady state the program inventory is 1 decode + 1
    prefill per bucket, and further streams compile NOTHING new."""
    tiny_serve.run(list(_stream(3, seed=5)))     # inventory warm (no-op if
    inv = tiny_serve.program_inventory()         # earlier tests ran first)
    assert inv["decode"] == 1
    assert inv["prefill_buckets"] == [16]   # prompts 3..13 share one bucket
    before = _compile_count()
    results = tiny_serve.run(list(_stream(6, seed=6)))   # same buckets
    assert len(results) == 6
    assert _compile_count() == before       # admission never recompiled
    assert tiny_serve.program_inventory() == inv


def test_serving_arrival_gating_and_gauges(tiny_engine, tiny_serve):
    mon = tiny_serve.monitor
    mon.events.clear()
    reqs = _stream(4, seed=7)
    for i, r in enumerate(reqs):
        r.arrival_time = 0.02 * i
    results = tiny_serve.run(list(reqs))
    _assert_parity(tiny_engine, results, reqs)
    for gauge in ("serve/queue_depth", "serve/active_slots",
                  "serve/slot_occupancy", "serve/free_pages",
                  "serve/tokens_per_sec"):
        assert mon.series(gauge), f"missing gauge {gauge}"
    ttfts = mon.series("serve/ttft_s")
    assert len(ttfts) == len(reqs)
    assert all(v >= 0 for _, v in ttfts)


def test_serving_submit_validation(tiny_engine, tiny_serve):
    with pytest.raises(ValueError, match="max_model_len"):
        tiny_serve.submit(Request(rid=0,
                                  input_ids=np.arange(60, dtype=np.int32),
                                  max_new_tokens=10))
    with pytest.raises(ValueError, match="empty"):
        tiny_serve.submit(Request(rid=1, input_ids=np.zeros((0,), np.int32)))
    with pytest.raises(ValueError):
        ServingEngine(tiny_engine.model, tiny_engine.params, b_slots=1,
                      page_size=16, max_model_len=64,
                      num_pages=2)   # cannot hold one slot
    # duplicate rids would corrupt the results map — rejected at submit
    tiny_serve.submit(Request(rid="dup", input_ids=np.array([1, 2, 3],
                                                            np.int32),
                              max_new_tokens=2))
    with pytest.raises(ValueError, match="unique"):
        tiny_serve.submit(Request(rid="dup", input_ids=np.array([4, 5],
                                                                np.int32),
                          max_new_tokens=2))
    (res,) = tiny_serve.run([])   # drain the queued original
    assert res.rid == "dup" and len(res.output_ids) == 2


def test_serving_prefill_failure_unwinds_reservation(tiny_engine, tiny_serve):
    """A prefill that dies on the device call must not leak pages or drop
    the request: the reservation unwinds and the request stays at the
    queue head for a retry."""
    real_prog = tiny_serve._exec._prefill_progs.get(16)

    def boom(*a, **k):
        raise RuntimeError("injected prefill failure")

    tiny_serve._exec._prefill_progs[16] = boom
    req = Request(rid="pf", input_ids=np.array([1, 2, 3], np.int32),
                  max_new_tokens=3)
    tiny_serve.submit(req)
    try:
        with pytest.raises(RuntimeError, match="injected prefill"):
            tiny_serve.step()
        # pages returned: the unwind may also have RECLAIMED cached-but-idle
        # prefix pages (free can grow), but nothing may leak
        assert tiny_serve.page_accounting()["balanced"]
        assert tiny_serve._queue[0].rid == "pf"             # still queued
        assert not tiny_serve._active.any()
    finally:
        if real_prog is None:
            del tiny_serve._exec._prefill_progs[16]
        else:
            tiny_serve._exec._prefill_progs[16] = real_prog
    (res,) = tiny_serve.run([])                             # retry succeeds
    assert res.rid == "pf" and len(res.output_ids) == 3


@pytest.mark.chaos
def test_serving_chaos_admission_delay_no_deadlock(tiny_engine, tiny_serve):
    """Satellite: a FaultInjector delay hook on admission + ticks must slow
    the loop, never wedge it — the stream completes exactly (seeded).  The
    shared fleet's pool (3 slots) is outsized by the 6-request stream, so
    admission blocks on busy slots while the injector stalls it."""
    inj = FaultInjector()
    inj.add(site=SITE_SERVE_ADMIT, kind="delay", delay_s=0.02, every=1,
            max_fires=4)
    inj.add(site=SITE_SERVE_TICK, kind="delay", delay_s=0.005, every=5,
            max_fires=3)
    install_injector(inj)
    try:
        reqs = _stream(6, seed=9)
        results = tiny_serve.run(list(reqs), max_ticks=2000)
    finally:
        clear_injector()
    assert len(inj.log) >= 4
    _assert_parity(tiny_engine, results, reqs)
    assert tiny_serve.page_accounting()["balanced"]


# ----------------------------------------------- cross-request KV reuse


def _shared_stream(n, seed, sys_len=21, tail_rng=(2, 6), max_new=5,
                   vocab=250, rid0=0):
    """Seeded stream of requests sharing one system prompt + unique tails.
    ``sys_len=21`` with page_size 8 = 2 full shared pages + a 5-token COW
    boundary; tails of 2-5 keep the boundary inside the partial page."""
    rng = np.random.default_rng(seed)
    system = rng.integers(1, vocab, sys_len).astype(np.int32)
    return [Request(rid=rid0 + i,
                    input_ids=np.concatenate(
                        [system, rng.integers(1, vocab,
                                              int(rng.integers(*tail_rng))
                                              ).astype(np.int32)]),
                    max_new_tokens=max_new)
            for i in range(n)]


@pytest.mark.slow
def test_prefix_sharing_token_exact_with_cow(tiny_engine):
    """Tentpole acceptance: requests sharing a system prompt map resident
    pages (incl. a copy-on-write boundary page) and stay token-exact with
    a no-sharing engine; the pool invariant holds and the program
    inventory never grows past the cold run's."""
    reqs = _shared_stream(6, seed=31)
    cold = tiny_engine.serving(b_slots=2, page_size=8, max_model_len=64,
                               prefix_cache=False)
    ref = {r.rid: r.output_ids for r in cold.run(
        [Request(rid=r.rid, input_ids=r.input_ids,
                 max_new_tokens=r.max_new_tokens) for r in reqs])}
    assert cold.prefix_hits == 0 and "cow" not in cold.program_inventory()

    serve = tiny_engine.serving(b_slots=2, page_size=8, max_model_len=64)
    results = serve.run(list(reqs))
    for r in results:
        np.testing.assert_array_equal(r.output_ids, ref[r.rid])
    # the donor was cold; every follower — INCLUDING request 1 — shares the
    # whole 21-token system prompt: the donor's page 3 is FULL, and a
    # partial prefix match inside a full donor page is COW-served (the
    # PR 6 carry-over closed in ISSUE 11), so the first follower no longer
    # drops to full-page granularity
    shared = {r.rid: r.shared_prefix_tokens for r in results}
    assert shared[reqs[0].rid] == 0
    assert all(v >= 21 for k, v in shared.items() if k != reqs[0].rid)
    assert serve.prefix_hits == 5 and serve.prefix_misses == 1
    assert serve.cow_copies == 5
    assert serve.prefix_pages_shared == 10          # 2 full pages x 5 hits
    assert serve.prefix_shared_tokens == sum(shared.values())
    acct = serve.page_accounting()
    assert acct["balanced"] and acct["referenced"] == acct["cached"]
    inv = serve.program_inventory()
    assert inv["cow"] == 1
    # a second shared batch admits with ZERO inventory growth
    results2 = serve.run(_shared_stream(4, seed=31, rid0=100))
    assert serve.program_inventory() == inv
    assert all(r.shared_prefix_tokens >= 21 for r in results2)


@pytest.mark.slow
def test_prefix_sharing_identical_prompts_cap_at_prompt_minus_one(
        tiny_engine):
    """An identical prompt shares at most L-1 tokens — the last prompt
    token always prefills so the first generated token has real logits."""
    serve = tiny_engine.serving(b_slots=2, page_size=8, max_model_len=64)
    prompt = np.arange(1, 18, dtype=np.int32)       # 17 tokens
    reqs = [Request(rid=i, input_ids=prompt.copy(), max_new_tokens=4)
            for i in range(3)]
    base = np.asarray(tiny_engine.generate(prompt[None],
                                           max_new_tokens=4))[0, 17:]
    results = serve.run(reqs)
    for r in results:
        np.testing.assert_array_equal(r.output_ids, base)
    assert {r.shared_prefix_tokens for r in results} == {0, 16}


def test_prefix_index_eviction_under_pool_pressure(tiny_engine):
    """Cached-but-idle pages must be reclaimed (LRU) when admission needs
    them — a full index never starves or deadlocks the pool."""
    # pool of 8 usable pages, 1 slot; each request needs 2-3 pages and
    # publishes entries that pin pages after retirement
    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=24,
                                num_pages=9)
    reqs = _stream(8, seed=33, smin=9, smax=14, new_choices=(4,))
    results = serve.run(list(reqs))
    assert len(results) == 8
    assert serve._prefix.evictions > 0              # pressure really evicted
    acct = serve.page_accounting()
    assert acct["balanced"] and acct["referenced"] == acct["cached"]


def test_prefix_index_unit():
    """PrefixIndex semantics: exact chunk verification, longest-common-
    prefix COW boundary, the L-1 cap via `limit`, and LRU eviction."""
    from deepspeed_tpu.inference.prefix_cache import PrefixIndex

    idx = PrefixIndex(page_size=4, max_entries=8)
    ids = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], np.int32)
    newly, released = idx.publish(ids, [11, 12, 13])   # 2 full + partial(2)
    assert newly == [11, 12, 13] and released == []
    assert sorted(idx.pages()) == [11, 12, 13]

    # full + boundary match, capped by limit
    m = idx.lookup(ids, limit=9)
    assert m.pages == [11, 12] and m.n_tokens == 9
    assert m.cow_src == 13 and m.cow_valid == 1     # limit clips the second
    # divergent second chunk: only chunk 0 matches, no boundary under h1'
    other = np.array([1, 2, 3, 4, 9, 9, 9, 9], np.int32)
    m = idx.lookup(other, limit=7)
    assert m.pages == [11] and m.n_tokens == 4 and m.cow_src is None
    # divergence INSIDE the partial chunk: longest common prefix wins
    part = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 77, 77], np.int32)
    m = idx.lookup(part, limit=10)
    assert m.pages == [11, 12] and m.cow_src == 13 and m.cow_valid == 1
    # re-publishing the identical prefix touches, never re-refs
    newly, released = idx.publish(ids, [11, 12, 13])
    assert newly == [] and released == []

    # LRU eviction returns pages for deref, oldest first
    for i in range(6):
        prompt = np.array([50 + i] * 5, np.int32)
        idx.publish(prompt, [20 + 2 * i, 21 + 2 * i])
    assert len(idx) <= 8
    assert idx.evictions > 0
    evicted = idx.evict(2)
    assert len(evicted) == 2
    assert all(p not in idx.pages() for p in evicted)


def test_prefix_collision_replacement_drops_stale_descendants(monkeypatch):
    """A chain-hash collision replaces the collided entry AND everything
    published under its chain (deeper full chunks + partial boundaries) —
    stale descendants verified against the new chain would otherwise map
    K/V computed under a different prefix."""
    from deepspeed_tpu.inference.prefix_cache import _ROOT, PrefixIndex

    idx = PrefixIndex(page_size=4, max_entries=16)
    a = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9], np.int32)
    idx.publish(a, [11, 12, 13])        # 2 full + partial(1)
    key0 = PrefixIndex._chain(_ROOT, (1, 2, 3, 4))

    def fake_chain(prev, chunk):        # simulated 64-bit collision:
        if prev == _ROOT and chunk == (9, 9, 9, 9):
            return key0                 # B's chunk 0 lands on A's key
        return hash((prev, chunk))

    monkeypatch.setattr(PrefixIndex, "_chain", staticmethod(fake_chain))
    newly, released = idx.publish(np.array([9, 9, 9, 9], np.int32), [20])
    assert newly == [20]
    assert sorted(released) == [11, 12, 13]   # A's whole subtree released
    m = idx.lookup(a, limit=9)                # degraded to a miss, not a
    assert m.pages == [] and m.cow_src is None  # wrong-page match


def test_head_matching_own_cached_prefix_admits_under_pressure(tiny_engine):
    """The queue head's own matched prefix being the only reclaimable
    cache must not read as an admission deadlock: reclaim evicts the
    entries, the admission pins were the last references, and the head
    retries with a fresh lookup against the freed pool."""
    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=40,
                                num_pages=6)        # 5 usable = one request
    prompt = np.arange(1, 21, dtype=np.int32)       # 2 full pages + 4
    (a,) = serve.run([Request(rid="a", input_ids=prompt,
                              max_new_tokens=20)])
    (b,) = serve.run([Request(rid="b", input_ids=prompt.copy(),
                              max_new_tokens=20)])  # needs ALL 5 pages
    np.testing.assert_array_equal(b.output_ids, a.output_ids)
    assert serve.page_accounting()["balanced"]


@pytest.mark.slow
def test_one_token_boundary_match_skips_cow(tiny_engine):
    """A boundary match below MIN_COW_TOKENS (e.g. two prompts sharing
    only their first token by chance) is not worth a pool-shaped page
    snapshot — the engine prefills the tail instead of COWing."""
    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=64)
    serve.run([Request(rid="d", input_ids=np.array([7, 1, 2], np.int32),
                       max_new_tokens=2)])
    (res,) = serve.run([Request(rid="f",
                                input_ids=np.array([7, 9, 9, 9], np.int32),
                                max_new_tokens=2)])
    assert serve.cow_copies == 0
    assert res.shared_prefix_tokens == 0


# ---------------------------------------------------------------- satellites


@pytest.mark.slow
def test_gen_cache_weakref_key_and_lru(tiny_engine):
    """Satellite: _gen_cache keys on weakref identity (id reuse after GC
    cannot alias a live entry) and is LRU-bounded."""
    import weakref

    engine = tiny_engine
    engine._gen_cache.clear()
    engine.generate(np.array([[1, 2, 3]]), max_new_tokens=4)
    assert len(engine._gen_cache) == 1
    (key,) = engine._gen_cache
    assert isinstance(key[0], weakref.ref)
    assert key[0]() is engine.model
    # same shape re-hit: no growth
    engine.generate(np.array([[4, 5, 6]]), max_new_tokens=4)
    assert len(engine._gen_cache) == 1

    # a cached program pins its model via closure (so an id can never be
    # recycled into a stale hit); eviction releases the pin — the weakref
    # key carries identity, the LRU cap bounds the pinning
    other = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    engine.generate(np.array([[1, 2]]), max_new_tokens=2, model=other,
                    params=engine.params)
    assert len(engine._gen_cache) == 2
    dead_ref = weakref.ref(other)
    del other
    gc.collect()
    assert dead_ref() is not None            # pinned while cached
    engine._gen_cache.clear()
    gc.collect()
    assert dead_ref() is None                # released with its entry

    # unhashable adapters (hash(ref) delegates to the referent) fall back
    # to the pinned-id key instead of crashing the cache lookup
    uh = type("UnhashableLM", (CausalLM,), {"__hash__": None})(
        "tiny", dtype=jnp.float32, attn_impl="xla")
    out = engine.generate(np.array([[1, 2, 3]]), max_new_tokens=2, model=uh,
                          params=engine.params)
    assert out.shape == (1, 5)
    assert any(isinstance(k[0], tuple) for k in engine._gen_cache)

    # LRU cap: many shapes never grow past GEN_CACHE_MAX (reuse the two
    # max_new values already compiled above + one new)
    old = engine.GEN_CACHE_MAX
    try:
        type(engine).GEN_CACHE_MAX = 2
        for m in (4, 2, 3):
            engine.generate(np.array([[1, 2, 3]]), max_new_tokens=m)
        assert len(engine._gen_cache) == 2
        # most-recent entries survive (key[3] is max_new_tokens)
        assert {k[3] for k in engine._gen_cache} == {2, 3}
    finally:
        type(engine).GEN_CACHE_MAX = old


def test_uncached_fallback_bounded_compiles():
    """Satellite: the full-recompute fallback pads to the bucket granularity
    — long generations compile O(1) programs, not one per token."""
    from tests.unit.test_inference import tiny_lm

    params, apply_fn = tiny_lm()
    engine = deepspeed_tpu.init_inference(config={"dtype": "float32"},
                                          apply_fn=apply_fn, params=params)
    out = engine.generate(np.array([[1, 2, 3]]), max_new_tokens=20)
    assert out.shape == (1, 23)
    # lengths 3..22 span buckets {16, 32} plus the one-time causality
    # probe's exact (1, 3) forward: three compiled programs, not 20
    assert engine._forward._cache_size() == 3
    assert engine._uncached_causal is True


def test_uncached_fallback_noncausal_drops_to_exact_path():
    """A non-causal apply_fn (pads would leak into earlier logits) must be
    detected by the one-time probe and served by the exact per-step loop."""
    rng = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(rng)
    params = {"emb": jax.random.normal(k1, (32, 16)) * 0.1,
              "out": jax.random.normal(k2, (16, 32)) * 0.1}

    def apply_fn(p, ids):
        h = p["emb"][ids]
        ctx = h.mean(axis=1, keepdims=True)   # sees the WHOLE row, pads too
        return (h + ctx) @ p["out"]

    engine = deepspeed_tpu.init_inference(config={"dtype": "float32"},
                                          apply_fn=apply_fn, params=params)
    out = np.asarray(engine.generate(np.array([[1, 2, 3]]),
                                     max_new_tokens=3))
    assert engine._uncached_causal is False
    # exact reference: the pre-bucketing growing-sequence loop
    ids = np.array([[1, 2, 3]])
    for _ in range(3):
        logits = np.asarray(apply_fn(params, jnp.asarray(ids)))
        ids = np.concatenate(
            [ids, logits[:, -1, :].argmax(-1)[:, None]], axis=1)
    np.testing.assert_array_equal(out, ids)


def test_eos_sentinel_never_emits_token_zero(tiny_engine):
    """Satellite: done rows repeat eos_id itself; with eos_token_id=None the
    -1 sentinel can never mark a row done, so no filler is ever emitted."""
    prompt = np.array([[5, 3, 9, 2]], np.int32)
    ref = np.asarray(tiny_engine.generate(prompt, max_new_tokens=6))
    eos = int(ref[0, 5])
    out = np.asarray(tiny_engine.generate(prompt, max_new_tokens=6,
                                          eos_token_id=eos))
    gen = out[0, 4:]
    hit = np.where(gen == eos)[0]
    assert len(hit) > 0
    assert (gen[hit[0]:] == eos).all()          # repeats eos, not token 0
    # eos=None output is identical to the no-eos reference (sentinel inert)
    out_none = np.asarray(tiny_engine.generate(prompt, max_new_tokens=6,
                                               eos_token_id=None))
    np.testing.assert_array_equal(out_none, ref)


@pytest.mark.slow
def test_quantized_engine_serving_parity():
    """Satellite (docs/SERVING.md carried item): a weight-quantized engine
    now serves through the paged path — the shimmed ``apply_paged``
    dequantizes at program entry, so serving is token-identical to
    quantized ``generate()`` (NOT to the fp32 engine: int8 weights round).
    """
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(3))
    qengine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32",
                             "quant": {"enabled": True, "num_bits": 8}},
        params=params)
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    assert any(isinstance(leaf, QuantizedWeight)
               for leaf in jax.tree_util.tree_leaves(
                   qengine.params,
                   is_leaf=lambda x: isinstance(x, QuantizedWeight)))
    serve = qengine.serving(b_slots=2, page_size=8, max_model_len=64)
    reqs = _stream(4, seed=41, new_choices=(4, 6))
    results = serve.run(list(reqs))
    _assert_parity(qengine, results, reqs)   # vs the QUANTIZED generate()
    assert serve.page_accounting()["balanced"]


@pytest.mark.slow
def test_serve_smoke_tool():
    """Satellite: tools/serve_smoke.py (the tier-1 compile-count assert)
    runs in-process — real jax.monitoring counters, no fresh interpreter."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), os.pardir, os.pardir, "tools"))
    try:
        from serve_smoke import run_smoke
    finally:
        sys.path.pop(0)
    out = run_smoke(n_requests=4)
    assert out["ok"], out
    assert out["steady_state_compiles"] == 0
    assert out["first_run_compiles"] <= out["compile_budget"]


def test_request_timeline_fields(tiny_engine, tiny_serve):
    """ISSUE 4: RequestResult carries a consistent per-request timeline —
    queued_s / ttft_s / decode_ticks / replays (docs/OBSERVABILITY.md)."""
    reqs = _stream(4, seed=21)
    for i, r in enumerate(reqs):
        r.arrival_time = 0.01 * i
    results = tiny_serve.run(list(reqs))
    assert len(results) == 4
    for r in results:
        # monotone stamps: arrival <= admit <= first token <= finish
        assert r.arrival_s <= r.admit_s <= r.first_token_s <= r.finish_s
        assert r.queued_s >= 0
        assert r.ttft_s >= r.queued_s          # first token needs admission
        assert r.latency_s >= r.ttft_s
        # the prefill emits tokens[0]; every other token is one decode tick
        assert r.decode_ticks == len(r.output_ids) - 1
        assert r.replays == 0                  # no supervisor, no restarts


# ------------------------------------------------ KV-page tiering (ISSUE 11)


@pytest.mark.slow
def test_mid_page_divergence_cow_from_full_donor_page(tiny_engine):
    """PR 6 carry-over closed: a prompt diverging INSIDE a donor's FULL
    page is COW-served up to the divergence point — the first follower
    after a donor no longer drops to full-page granularity."""
    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=64)
    cold = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=64,
                               prefix_cache=False)
    donor_ids = np.arange(1, 20, dtype=np.int32)       # 2 full pages + 3
    follower_ids = np.concatenate(                     # diverges at tok 12,
        [donor_ids[:12], np.array([99, 98, 97], np.int32)])  # inside page 2
    (ref,) = cold.run([Request(rid="f", input_ids=follower_ids.copy(),
                               max_new_tokens=4)])
    serve.run([Request(rid="d", input_ids=donor_ids, max_new_tokens=4)])
    (res,) = serve.run([Request(rid="f", input_ids=follower_ids,
                                max_new_tokens=4)])
    np.testing.assert_array_equal(res.output_ids, ref.output_ids)
    # page 1 mapped whole + the donor's FULL page 2 COW'd for its first
    # 4 matching tokens = 12 shared prompt tokens, one snapshot
    assert res.shared_prefix_tokens == 12
    assert serve.cow_copies == 1
    assert serve.page_accounting()["balanced"]


def test_prefix_index_full_chunk_divergence_is_cow_candidate():
    """Index half of the carry-over: lookup offers a full entry as COW
    source when the prompt diverges inside it (and never a demoted one)."""
    from deepspeed_tpu.inference.prefix_cache import PrefixIndex

    idx = PrefixIndex(page_size=4, max_entries=8)
    ids = np.array([1, 2, 3, 4, 5, 6, 7, 8], np.int32)
    idx.publish(ids, [11, 12])                        # 2 full chunks
    div = np.array([1, 2, 3, 4, 5, 6, 9, 9], np.int32)
    m = idx.lookup(div, limit=8)
    assert m.pages == [11] and m.keys and m.n_tokens == 6
    assert m.cow_src == 12 and m.cow_valid == 2       # inside full chunk 1
    # a demoted donor is no COW candidate (its page is on the host tier)
    key1 = m.keys[0]
    m_full = idx.lookup(ids, limit=8)
    idx.demote(m_full.keys[1])
    m2 = idx.lookup(div, limit=8)
    assert m2.cow_src is None and m2.pages == [11]
    assert key1 == m2.keys[0]


def test_prefix_index_demote_promote_and_digest():
    """Tiering state machine on the index: demote frees the page but keeps
    the entry matchable (-1), promote restores it, removal of a demoted
    entry fires on_drop_host, and the digest reports (chain_key, tier)."""
    from deepspeed_tpu.inference.prefix_cache import PrefixIndex, chain_keys

    idx = PrefixIndex(page_size=4, max_entries=8)
    ids = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9], np.int32)
    idx.publish(ids, [11, 12, 13])                    # 2 full + partial
    keys = chain_keys(ids, 4)
    assert [k for k, _ in idx.digest()][::-1] == keys  # MRU-first

    cand = idx.reclaim_candidate()
    assert cand is not None and cand[0] == keys[0]     # LRU-most HBM entry
    assert idx.demote(keys[0]) == 11
    assert idx.demoted == 1 and idx.hbm_entries() == 2
    m = idx.lookup(ids, limit=9)
    assert m.pages == [-1, 12] and m.keys == keys      # still matchable
    assert dict(idx.digest())[keys[0]] == 1            # host tier code
    idx.promote(keys[0], 21)
    assert idx.demoted == 0
    assert idx.lookup(ids, limit=9).pages == [21, 12]

    dropped = []
    idx.on_drop_host = dropped.append
    idx.demote(keys[1])
    assert idx.evict_key(keys[1]) is None              # no device page
    assert dropped == [keys[1]] and idx.demoted == 0
    # partial entries never demote (the boundary entry lives under the
    # chain key of the last full chunk)
    with pytest.raises(ValueError):
        idx.demote(("p", keys[1], (9,)))
    # a FULL destination index adopts nothing (the lst[-0:] trap)
    donor = PrefixIndex(page_size=4, max_entries=8)
    donor.publish(ids, [31, 32, 33])
    donor.demote(chain_keys(ids, 4)[0])
    full_idx = PrefixIndex(page_size=4, max_entries=2)
    full_idx.publish(np.array([7, 7, 7, 7, 8, 8, 8, 8], np.int32), [41, 42])
    assert full_idx.adopt_demoted(donor) == []
    assert full_idx.demoted == 0 and len(full_idx) == 2


def test_host_tier_unit():
    """HostTier storage semantics: LRU order, byte accounting, capacity,
    idempotent discard, adoption with a budget."""
    from deepspeed_tpu.inference.kv_tiering import HostTier

    tier = HostTier(max_pages=2, page_bytes=64)
    a = np.zeros((2, 4, 1, 2), np.float32)
    tier.put("k1", a, a)
    tier.put("k2", a, a)
    assert len(tier) == 2 and tier.full()
    assert tier.bytes() == 4 * a.nbytes
    assert tier.oldest_key() == "k1"
    tier.touch("k1")
    assert tier.oldest_key() == "k2"
    assert tier.get("k2") is not None                  # get touches too
    assert tier.oldest_key() == "k1"
    tier.discard("k1")
    tier.discard("k1")                                 # idempotent
    assert len(tier) == 1 and tier.bytes() == 2 * a.nbytes
    assert tier.pop("missing") is None

    other = HostTier(max_pages=4)
    for k in ("a", "b", "c"):
        other.put(k, a, a)
    small = HostTier(max_pages=2)
    adopted = small.adopt(other)
    assert adopted == ["b", "c"]                       # MRU-most survive
    with pytest.raises(ValueError):
        HostTier(max_pages=0)


@pytest.mark.slow
def test_serving_tiering_demote_promote_token_exact(tiny_engine):
    """Tentpole acceptance (engine level): under pool pressure the engine
    DEMOTES cold prefix pages instead of evicting, promotes them on the
    next hit, stays token-exact with an untiered engine, keeps the
    extended accounting invariant balanced, and never grows the program
    inventory past init's."""
    rng = np.random.default_rng(7)
    systems = [rng.integers(1, 250, 17).astype(np.int32) for _ in range(3)]
    tails = [rng.integers(1, 250, 3).astype(np.int32) for _ in range(9)]

    def stream(rid0=0):
        return [Request(rid=rid0 + i,
                        input_ids=np.concatenate([systems[i % 3], tails[i]]),
                        max_new_tokens=4)
                for i in range(9)]

    ref_serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=40,
                                    num_pages=8, prefix_cache=False)
    ref = {r.rid % 100: r.output_ids for r in ref_serve.run(stream())}
    del ref_serve

    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=40,
                                num_pages=8, host_tier_pages=16)
    assert serve.program_inventory()["tier"] == {"extract": 1, "inject": 1}
    results = serve.run(stream())
    inv = serve.program_inventory()   # buckets warm after the first batch
    for r in results:
        np.testing.assert_array_equal(r.output_ids, ref[r.rid])
    assert serve.demotions > 0 and serve.promotions > 0
    acct = serve.page_accounting()
    assert acct["balanced"] and acct["demoted"] == len(serve._tier)
    assert acct["host_tier_bytes"] == serve._tier.bytes()
    # rotation round 2: every system prompt hits (hot or promoted), and
    # demote/promote cycling never grows the inventory
    results2 = serve.run(stream(rid0=100))
    for r in results2:
        np.testing.assert_array_equal(r.output_ids, ref[r.rid % 100])
    assert all(r.shared_prefix_tokens > 0 for r in results2)
    assert serve.program_inventory() == inv
    h = serve.health()
    assert h["demoted_pages_hwm"] >= h["demoted_pages"]
    lat = serve.tier_latencies()
    assert len(lat["promote_s"]) == serve.promotions
    assert len(lat["demote_s"]) == serve.demotions
    assert serve.residency_digest()
    # gauges (the tier quartet) land on the monitor path via health/acct —
    # exposition coverage lives in test_observability.py
    with pytest.raises(ValueError, match="prefix_cache"):
        tiny_engine.serving(b_slots=1, page_size=8, max_model_len=40,
                            prefix_cache=False, host_tier_pages=4)


def test_host_tier_capacity_evicts_for_real(tiny_engine):
    """A full host tier evicts its LRU buffer AND the index entry — the
    one place tiering still loses cache — with the ledger balanced."""
    rng = np.random.default_rng(11)
    systems = [rng.integers(1, 250, 17).astype(np.int32) for _ in range(4)]

    def req(i, rid):
        return Request(rid=rid,
                       input_ids=np.concatenate(
                           [systems[i],
                            rng.integers(1, 250, 3).astype(np.int32)]),
                       max_new_tokens=4)

    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=40,
                                num_pages=8, host_tier_pages=2)
    serve.run([req(i, i) for i in range(4)])
    assert serve.demotions > 0
    assert len(serve._tier) <= 2
    acct = serve.page_accounting()
    assert acct["balanced"] and acct["demoted"] <= 2
    assert serve._prefix.demoted == len(serve._tier)
