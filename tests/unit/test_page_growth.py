"""A slot's pages follow its length (docs/SERVING.md "Scheduling policy"):
admission reserves the prompt's pages and the first decode row's, a live slot
takes one more page before the tick whose row starts it is launched, and when
the pool has none the slot admitted last gives its pages up and its request is
readmitted at the head of the queue, its rows rebuilt by the prefill programs
already built.  What it must hold: every request's stream is the one a pool of
the full reservation emits, each token once; the pool's accounts balance after
every tick; a pool of the full reservation never preempts and admits as the
whole reservation did; the victim is the youngest and the oldest always
finishes; a readmission compiles nothing; a cache a tail prefill cannot
rebuild keeps the whole reservation.

All CPU, the traffic file's ``rehearse`` geometry: 4 slots, pages of 16, 10
pages."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.inference.speculative import (SpeculativeConfig,
                                                 layer_skip_draft)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.utils.compile_counter import compile_counter

from .test_cache_layout import CONFIGS

_compiles = compile_counter()

GEO = dict(b_slots=4, page_size=16, max_model_len=128)
BOUND = dict(num_pages=10, **GEO)       # 9 pages where 32 are the whole
COUNTERS = ("page_grows_total", "preemptions_total",
            "recomputed_tokens_total")
LANES = {"greedy": None,
         "sampled": SamplingParams(temperature=0.8, top_k=20, seed=11)}


@pytest.fixture(scope="module")
def engine():
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    return deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"},
        params=model.init_fn(jax.random.PRNGKey(3)))


def _backlog(n=14, seed=1, prompt=(4, 60), new=(8, 60), **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", max_new_tokens=int(rng.integers(*new)),
                    input_ids=rng.integers(1, 200, (int(rng.integers(*prompt)),)
                                           ).astype(np.int32), **kw)
            for i in range(n)]


def _streams(results):
    return {r.rid: list(r.output_ids) for r in results}


def _run_by_hand(sv, requests, each_tick=lambda: None, limit=4000):
    for r in requests:
        sv.submit(r)
    t = 0
    while sv.step(now=float(t)):
        each_tick()
        t += 1
        assert t < limit, "the backlog does not drain"
    each_tick()
    return sv.take_results()


@pytest.fixture(scope="module")
def whole_streams(engine):
    """What a pool of the full reservation emits, greedy and sampled."""
    out = {}
    for name, lane in LANES.items():
        sv = engine.serving(**GEO)
        out[name] = _streams(sv.run(_backlog(sampling=lane)))
        assert sv.health()["preemptions_total"] == 0
    return out


# ------------------------------------------ (a) the streams, each token once

@pytest.mark.parametrize("lane", sorted(LANES))
def test_a_page_bound_pool_emits_the_full_reservations_streams(
        engine, whole_streams, lane):
    sv = engine.serving(**BOUND)
    requests = _backlog(sampling=LANES[lane])
    results = sv.run(requests)
    h = sv.health()
    assert h["preemptions_total"] >= 3 and h["page_grows_total"] > 0
    assert h["recomputed_tokens_total"] > 0 and h["preempted_waiting"] == 0
    # one result a rid, every token and its stamp, the stream of the pool
    # that never preempts
    assert sorted(r.rid for r in results) == sorted(q.rid for q in requests)
    assert _streams(results) == whole_streams[lane]
    asked = {q.rid: q.max_new_tokens for q in requests}
    for r in results:
        assert r.finish_reason == "length"
        assert len(r.output_ids) == asked[r.rid] == len(r.token_s)
        assert (np.diff(r.token_s) >= 0).all()
        assert r.token_s[0] == r.first_token_s >= r.admit_s
        # a readmission's prefill emits a token in a tick's place
        assert (len(r.output_ids) - 1 - r.preemptions <= r.decode_ticks
                <= len(r.output_ids) - 1)
        events = [e[0] for e in r.lifecycle]
        assert events.count("preempt") == r.preemptions
        assert events.count("admit") == 1 + r.preemptions
        assert events.count("first_token") == events.count("finish") == 1
    assert sum(r.preemptions for r in results) == h["preemptions_total"]
    # no token emitted or counted twice
    assert sv._tokens_out == sum(asked.values())
    assert sv.page_accounting()["balanced"] and not sv._preempted


# --------------------------------------- (b) the accounts, after every tick

def _shared_prefix_backlog():
    """Two pages of prompt in common: a victim's shared pages drop a
    reference and are not freed."""
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, 200, (35,)).astype(np.int32)
    return [Request(rid=f"s{i}", max_new_tokens=int(rng.integers(20, 60)),
                    input_ids=np.concatenate([prefix, rng.integers(
                        1, 200, (int(rng.integers(2, 12)),)).astype(np.int32)]))
            for i in range(10)]


@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["prefix-index", "no-index"])
@pytest.mark.parametrize("backlog", [_backlog, _shared_prefix_backlog],
                         ids=["distinct", "shared-prefix"])
def test_the_pool_balances_after_every_tick(engine, prefix_cache, backlog):
    sv = engine.serving(prefix_cache=prefix_cache, **BOUND)
    want = _streams(engine.serving(prefix_cache=prefix_cache, **GEO).run(
        backlog()))

    def balanced():
        acct = sv.page_accounting()
        assert acct["balanced"], acct
        # a live slot's row names the pages it holds, and no page twice
        for slot in np.flatnonzero(sv._active):
            st = sv._slots[slot]
            assert sv._pages.row(slot) == st.pages
            assert len(set(st.pages)) == len(st.pages)
            assert len(st.pages) * sv.page_size >= sv._lengths[slot]

    results = _run_by_hand(sv, backlog(), balanced)
    assert _streams(results) == want
    h = sv.health()
    assert h["preemptions_total"] >= 1
    acct = sv.page_accounting()
    # at rest the index's pins are the only references left
    assert acct["referenced"] == acct["cached"]
    if prefix_cache and backlog is _shared_prefix_backlog:
        assert h["prefix_hits_total"] > 0


# ------------------------- (c) the full reservation: nothing moves, no victim

def test_a_pool_of_the_full_reservation_admits_as_the_whole_reservation_did(
        engine):
    """Tick for tick beside an engine that takes the whole reservation at
    admission (the parent's rule, set on the instance): the same requests
    live in the same slots at the same lengths, the same launches, the same
    inventory; only which physical page holds a row may differ."""
    grow, whole = engine.serving(**GEO), engine.serving(**GEO)
    whole._grow = False
    for sv in (grow, whole):
        for r in _backlog(n=12, seed=4):
            sv.submit(r)
    t = 0
    while True:
        left = [sv.step(now=float(t)) for sv in (grow, whole)]
        assert left[0] == left[1]
        assert np.array_equal(grow._active, whole._active)
        assert np.array_equal(grow._lengths, whole._lengths)
        assert grow._launch_seq == whole._launch_seq
        for slot in np.flatnonzero(grow._active):
            a, b = grow._slots[slot], whole._slots[slot]
            assert a.request.rid == b.request.rid and a.order == b.order
            # the entries that hold rows (and the next tick's row)
            live = -(-(int(grow._lengths[slot]) + 1) // grow.page_size)
            assert (grow._pages.table[slot, :live] > 0).all()
            assert (whole._pages.table[slot, :live] > 0).all()
            assert len(a.pages) <= len(b.pages)
        t += 1
        if not left[0]:
            break
    assert _streams(grow.take_results()) == _streams(whole.take_results())
    assert grow.program_inventory() == whole.program_inventory()
    g, w = grow.health(), whole.health()
    assert g["preemptions_total"] == g["recomputed_tokens_total"] == 0
    assert g["page_grows_total"] > 0 == w["page_grows_total"]
    assert g["admission_page_waits_total"] == 0
    for key in ("lookahead_launched_total", "lookahead_stale_taken_total",
                "lookahead_dropped_total", "prefill_fed_on_device_total"):
        assert g[key] == w[key], key
    assert g["pages_hwm"] <= w["pages_hwm"]


def test_a_page_boundary_costs_no_launched_tick(engine):
    """Every slot busy, eight ticks in flight, each slot crossing page
    boundaries: a launched tick is taken for a slot whose row has since
    gained a page behind the rows it read and wrote."""
    sv = engine.serving(b_slots=3, page_size=8, max_model_len=64)
    rng = np.random.default_rng(2)
    results = sv.run([Request(rid=f"b{i}", max_new_tokens=40,
                              input_ids=rng.integers(1, 200, (5 + i,)).astype(
                                  np.int32)) for i in range(3)])
    h = sv.health()
    assert all(len(r.output_ids) == 40 for r in results)
    assert h["page_grows_total"] >= 3 * 4
    assert h["lookahead_launched_total"] > 30
    assert h["lookahead_stale_taken_total"] == h["lookahead_dropped_total"] == 0


# ------------------------ (d) the victim is the youngest; the oldest finishes

def test_the_youngest_gives_up_and_the_oldest_always_finishes(
        engine, monkeypatch):
    """A first request that grows to seven of the nine pages, behind it a
    stream of short ones that would take every page it frees."""
    rng = np.random.default_rng(9)

    def ids(n):
        return rng.integers(1, 200, (n,)).astype(np.int32)

    requests = [Request(rid="old", input_ids=ids(10), max_new_tokens=100)] + [
        Request(rid=f"y{i}", input_ids=ids(int(rng.integers(8, 30))),
                max_new_tokens=int(rng.integers(10, 40))) for i in range(16)]
    want = _streams(engine.serving(**GEO).run(requests))
    sv = engine.serving(**BOUND)
    victims = []
    preempt = ServingEngine._preempt

    def spy(self, slot):
        live = [self._slots[i].order for i in np.flatnonzero(self._active)]
        victims.append((self._slots[slot].request.rid,
                        self._slots[slot].order, max(live), len(live)))
        return preempt(self, slot)

    monkeypatch.setattr(ServingEngine, "_preempt", spy)
    results = {r.rid: r for r in sv.run(requests)}
    assert _streams(results.values()) == want
    assert len(victims) >= 3
    for rid, order, youngest, live in victims:
        assert order == youngest and rid != "old"
    assert results["old"].preemptions == 0
    assert results["old"].finish_reason == "length"
    # a request keeps its place in admission order over a readmission
    again = {rid for rid, *_ in victims
             if sum(1 for v in victims if v[0] == rid) > 1}
    assert again
    for rid in again:
        assert len({order for r, order, _, _ in victims if r == rid}) == 1
    assert sv.health()["preemptions_total"] == len(victims)


def test_one_slot_may_own_the_whole_pool(engine):
    """A request as long as the whole pool beside others (``submit`` refuses
    only what the pool can never hold): every younger slot gives up for it,
    it finishes, and then they do."""
    rng = np.random.default_rng(5)
    sv = engine.serving(**{**BOUND, "num_pages": 9})
    requests = [Request(rid="all", max_new_tokens=100,
                        input_ids=rng.integers(1, 200, (28,)).astype(np.int32))
                ] + _backlog(n=6, seed=8)
    assert sv._pages_whole(requests[0]) == sv.num_pages - 1
    want = _streams(engine.serving(**GEO).run(requests))
    results = {r.rid: r for r in sv.run(requests)}
    assert _streams(results.values()) == want
    assert results["all"].preemptions == 0
    assert sv.health()["preemptions_total"] >= 1
    assert sv.page_accounting()["balanced"]


# ------------------------------------- (e) a readmission compiles nothing

def test_a_readmission_longer_than_every_built_bucket_compiles_nothing(engine):
    """Only the 16-token prefill program is built; a request that gave its
    pages up at 50 rows and more is rebuilt in pieces of 16."""
    rng = np.random.default_rng(12)

    def requests(tag):
        return [Request(rid=f"{tag}{i}", max_new_tokens=int(n),
                        input_ids=rng.integers(1, 200, (int(p),)).astype(
                            np.int32))
                for i, (p, n) in enumerate(zip(
                    np.random.default_rng(13).integers(5, 16, 10),
                    np.random.default_rng(14).integers(50, 100, 10)))]

    sv = engine.serving(**BOUND)
    sv.run(requests("warm")[:1])
    before = sv.program_inventory()
    assert before["prefill_buckets"] == [16]
    stream = requests("q")
    want = {r.rid: list(r.output_ids)
            for r in engine.serving(**GEO).run(stream)}
    pieces = []
    launch = sv._launch_prefill

    def spy(s_pad, slot, toks, n_real, start, *rest):
        pieces.append((s_pad, n_real, start))
        return launch(s_pad, slot, toks, n_real, start, *rest)

    sv._launch_prefill = spy
    c0 = _compiles()
    results = sv.run(stream)
    assert _compiles() == c0
    assert sv.program_inventory() == before
    assert _streams(results) == want
    h = sv.health()
    assert h["preemptions_total"] >= 1
    # pieces behind rows already rebuilt, none longer than the bucket
    tails = [p for p in pieces if p[2] > 0]
    assert tails and all(s_pad == 16 >= n for s_pad, n, _ in pieces)
    assert max(start + n for _, n, start in pieces) > 48
    assert h["recomputed_tokens_total"] == sum(
        n for _, n, start in pieces) - sum(len(q.input_ids) for q in stream)


# ----------------------- (f) what cannot be rebuilt keeps its reservation

def _tiny_of(kind):
    cfg = CONFIGS[kind]()
    model = CausalLM(cfg)
    return deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"},
        params=model.init_fn(jax.random.PRNGKey(1)))


def _speculative(engine):
    dm, dp = layer_skip_draft(engine.model, engine.params, 1)
    return engine, dict(speculative=SpeculativeConfig(
        draft_model=dm, draft_params=dp, k=3))


@pytest.mark.parametrize("case", ["window", "latent", "state", "speculative"])
def test_a_cache_no_tail_prefill_rebuilds_keeps_the_whole_reservation(
        engine, case):
    eng, kw = (_speculative(engine) if case == "speculative"
               else (_tiny_of(case), {}))
    sv = eng.serving(**BOUND, **kw)
    assert sv._grow is False
    requests = _backlog(n=8, seed=3, prompt=(4, 40), new=(8, 50))
    for r in requests:
        assert sv._pages_needed(r) == sv._pages_whole(r) == -(
            -(len(r.input_ids) + r.max_new_tokens) // 16)
    results = sv.run(requests)
    assert all(len(r.output_ids) == q.max_new_tokens
               for r, q in zip(sorted(results, key=lambda r: int(r.rid[1:])),
                               requests))
    h = sv.health()
    # pages, not slots, bound the batch; no slot ever asked for a page
    assert h["admission_page_waits_total"] > 0
    assert [h[k] for k in COUNTERS] == [0, 0, 0]
    assert sv.page_accounting()["balanced"]


def test_the_engines_that_grow_are_the_tables_row(engine):
    sv = engine.serving(**BOUND)
    assert sv._grow is True
    r = Request(rid="x", max_new_tokens=50,
                input_ids=np.ones((20,), np.int32))
    assert (sv._pages_needed(r), sv._pages_whole(r)) == (2, 5)
    r1 = Request(rid="y", max_new_tokens=1, input_ids=np.ones((16,), np.int32))
    assert sv._pages_needed(r1) == sv._pages_whole(r1) == 2


# ------------------------------------------------------------ the counters

def test_counters_ride_health_gauges_and_spans(engine):
    from deepspeed_tpu.observability.trace import configure_tracer

    class Monitor:
        def __init__(self):
            self.last = {}

        def write_events(self, events):
            self.last.update({name: value for name, value, _ in events})

    mon = Monitor()
    tracer = configure_tracer(enabled=True, capacity=1 << 15)
    tracer.reset()
    try:
        sv = engine.serving(monitor=mon, **BOUND)
        sv.run(_backlog())
        h = sv.health()
        for key in COUNTERS:
            assert h[key] > 0 and mon.last[f"serve/{key}"] == h[key]
        spans = [s for s in tracer.recorder.snapshot() if hasattr(s, "attrs")]
        ticks = [s.attrs for s in spans if s.name == "serve.tick"
                 and "preemptions" in (s.attrs or {})]
        assert ticks[-1]["preemptions"] == h["preemptions_total"]
        assert ticks[-1]["page_grows"] == h["page_grows_total"]
        assert ticks[-1]["recomputed_tokens"] == h["recomputed_tokens_total"]
        gave_up = [s for s in spans if s.name == "serve.preempt"]
        assert len(gave_up) == h["preemptions_total"]
        admits = [s.attrs for s in spans if s.name == "serve.admit"]
        assert sum(1 for a in admits if a["emitted"] > 0) >= 1
        assert all(a["pages"] >= 1 for a in admits)
    finally:
        configure_tracer(enabled=False)
        tracer.reset()


# ------------------------------- who else runs it: the engine's other shapes

SHAPES = {"plain-loop": dict(lookahead=False),
          "host-tier": dict(host_tier_pages=8),
          "int8-pool": dict(kv_dtype="int8"),
          "stop-token": {}}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_engines_other_shapes_keep_their_streams(engine, whole_streams,
                                                     shape):
    """The plain loop, the host tier under the reclaim that runs before a
    preemption, an int8 pool, and requests that can stop on a token the
    host has not seen: a page-bound pool emits what the full reservation of
    the same shape emits."""
    kw = SHAPES[shape]
    lane = {}
    if shape == "stop-token":
        # a token some streams hold past their third: they end on it
        stop = int(np.bincount([t for out in whole_streams["greedy"].values()
                                for t in out[3:]]).argmax())
        lane = dict(eos_token_id=stop)
    want = engine.serving(**GEO, **kw).run(_backlog(**lane))
    sv = engine.serving(**BOUND, **kw)
    got = sv.run(_backlog(**lane))
    assert {r.rid: (list(r.output_ids), r.finish_reason) for r in got} == {
        r.rid: (list(r.output_ids), r.finish_reason) for r in want}
    if shape == "stop-token":
        assert any(r.finish_reason == "eos" for r in got)
    h = sv.health()
    assert h["preemptions_total"] >= 1 and sv.page_accounting()["balanced"]
    if shape == "host-tier":
        assert h["demotions_total"] > 0


@pytest.mark.parametrize("site,at_call", [("serve.decode", 25),
                                          ("serve.prefill", 9)])
def test_a_warm_restart_of_a_page_bound_engine_replays_exactly(
        engine, whole_streams, site, at_call):
    from deepspeed_tpu.resilience import (FaultInjector, clear_injector,
                                          install_injector)

    sup = engine.supervised_serving(**BOUND)
    install_injector(FaultInjector()).add(site=site, kind="raise",
                                          at_call=at_call)
    try:
        results = sup.run(_backlog(), max_ticks=5000)
    finally:
        clear_injector()
    assert _streams(results) == whole_streams["greedy"]
    assert all(len(r.output_ids) == len(r.token_s) for r in results)
    h = sup.health()
    assert sup.restarts == (site == "serve.decode")
    # the counters are carried over the restart
    assert h["preemptions_total"] >= 3 and h["page_grows_total"] > 0
    assert sup.engine.page_accounting()["balanced"]


def test_a_restart_carries_what_a_waiting_request_had_emitted(
        engine, whole_streams):
    """The fault lands in the tick after a request with tokens gave its
    pages up: the replacement engine replays it with those tokens, like a
    slot in flight, and emits none of them again."""
    from deepspeed_tpu.resilience import (FaultInjector, clear_injector,
                                          install_injector)

    dry = engine.serving(**BOUND)
    for r in _backlog():
        dry.submit(r)
    ticks = 0
    while not any(st.tokens for st in dry._preempted.values()):
        assert dry.step(now=0.0)
        ticks += 1                      # a decode tick a step here
    rid, st = next((rid, st) for rid, st in dry._preempted.items()
                   if st.tokens)
    carried = list(st.tokens)
    sup = engine.supervised_serving(**BOUND)
    install_injector(FaultInjector()).add(site="serve.decode", kind="raise",
                                          at_call=ticks + 1)
    try:
        results = {r.rid: r for r in sup.run(_backlog(), max_ticks=5000)}
    finally:
        clear_injector()
    assert sup.restarts == 1
    assert _streams(results.values()) == whole_streams["greedy"]
    r = results[rid]
    assert r.replays == 1 and list(r.output_ids[:len(carried)]) == carried
    # the replacement decoded only what was left
    assert r.decode_ticks <= len(r.output_ids) - 1


def test_a_request_waiting_to_be_readmitted_expires_with_what_it_emitted(
        engine):
    sv = engine.serving(**BOUND)
    for r in _backlog(deadline_s=1000.0):
        sv.submit(r)
    t = 0
    while not any(st.tokens for st in sv._preempted.values()):
        assert sv.step(now=float(t))
        t += 1
    rid, st = next((rid, st) for rid, st in sv._preempted.items()
                   if st.tokens)
    emitted, stamps = list(st.tokens), list(st.token_s)
    sv.step(now=5000.0)
    (r,) = [r for r in sv.take_results() if r.rid == rid]
    assert r.finish_reason == "deadline" and r.preemptions >= 1
    assert list(r.output_ids) == emitted and list(r.token_s) == stamps
    assert r.first_token_s == stamps[0]
    assert not sv._preempted and sv._waiting_deadlines == 0
    assert sv.page_accounting()["balanced"]
