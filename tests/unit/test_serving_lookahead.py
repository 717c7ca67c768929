"""Decode lookahead (docs/SERVING.md "Decode lookahead"): the serving loop
keeps decode ticks launched ahead of the one it fetches, each on the
device-resident tokens of the one before, each under its own mask and
lengths: up to ``LOOKAHEAD_TICKS`` where no arrival can be placed (one of
them past a slot's end while a request waits for the slot), one where a slot
is free for an arrival, none over a request that waits.  An admission's
prefill is launched behind them and its first token feeds the next tick on
the device.  What it must hold: the streams are the ones the plain loop
emits, it is one compiled decode program, a launched tick is taken for every
slot whose own inputs it was launched on and for no other, no fetch stands
between two launches, and an arrival's prefill is never launched behind more
than one decode program nor costs the live streams a tick."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.inference.serving import (LOOKAHEAD_TICKS,
                                             PREFILLS_IN_FLIGHT, Request)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.utils.compile_counter import compile_counter

from .test_tracing_names import _Clock      # the engine's clock, injected

_compiles = compile_counter()

MODELS = {"dense": ("tiny", {}),
          "moe": ("tiny-moe", {"moe_drop_tokens": False})}


@pytest.fixture(scope="module", params=sorted(MODELS))
def engine(request):
    name, overrides = MODELS[request.param]
    model = CausalLM(name, dtype=jnp.float32, attn_impl="xla", **overrides)
    return deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"},
        params=model.init_fn(jax.random.PRNGKey(3)))


greedy_and_sampled = pytest.mark.parametrize("sampling", [None, SamplingParams(
    temperature=0.8, top_k=20, seed=11)], ids=["greedy", "sampled"])


@pytest.fixture
def tracer():
    from deepspeed_tpu.observability.trace import configure_tracer

    tracer = configure_tracer(enabled=True, capacity=4096)
    tracer.reset()
    yield tracer
    configure_tracer(enabled=False)
    tracer.reset()      # the ring is the process's: leave the next file none


def _serving(engine, lookahead, **kw):
    return engine.serving(**{**dict(b_slots=3, page_size=8, max_model_len=64,
                                    lookahead=lookahead), **kw})


def _requests(n=9, seed=1, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", max_new_tokens=int(rng.integers(2, 30)),
                    input_ids=rng.integers(1, 200, (int(rng.integers(3, 20)),)
                                           ).astype(np.int32), **kw)
            for i in range(n)]


def _streams(results):
    return {r.rid: (list(r.output_ids), r.finish_reason) for r in results}


@greedy_and_sampled
def test_lookahead_streams_are_the_plain_loops(engine, sampling):
    plain = _serving(engine, False)
    ahead = _serving(engine, True)
    c0 = _compiles()
    want = _streams(plain.run(_requests(sampling=sampling)))
    c1 = _compiles()
    got = _streams(ahead.run(_requests(sampling=sampling)))
    assert got == want
    assert plain.lookahead_launched == 0
    assert ahead.lookahead_launched > ahead._tick // 2
    # every launched tick was someone's, and none is left in flight
    assert ahead.lookahead_dropped == 0 and not ahead._ahead
    assert not ahead._firsts and ahead.prefill_fed_on_device > 0
    # the fed-back output is the same program's input: no second compile
    assert ahead._exec._decode_prog._cache_size() == 1
    assert _compiles() - c1 == c1 - c0


def test_no_lookahead_past_a_stop_the_host_has_not_seen(engine):
    """A live request that can end on a token is not run ahead of."""
    sv = _serving(engine, True)
    want = _streams(_serving(engine, False).run(_requests(eos_token_id=7)))
    assert _streams(sv.run(_requests(eos_token_id=7))) == want
    assert sv.lookahead_launched == 0


def test_one_tick_ahead_while_a_slot_is_free_for_an_arrival(engine):
    """With a slot free and an arrival still to come exactly one tick is in
    flight after every step, never more: all the arrival can find ahead of
    its prefill."""
    sv = _serving(engine, True)
    now, later = _requests(2)
    now.max_new_tokens, later.arrival_time = 12, 1e9
    sv.submit(now)
    sv.submit(later)
    depths = []
    for _ in range(6):
        sv.step(now=0.0)
        depths.append(len(sv._ahead))
    assert depths == [1] * 6
    assert sv.lookahead_launched == 6 and sv.lookahead_dropped == 0


def _launch_log(sv):
    """Every device program ``sv`` launches from here on, in order:
    ``("decode", ticks in flight at the launch)`` or ``("prefill", rid)``."""
    log = []
    decode, prefill = sv._exec.decode, sv._prefill

    def logged_decode(*a, **kw):
        log.append(("decode", len(sv._ahead)))
        return decode(*a, **kw)

    def logged_prefill(slot, req, *a, **kw):
        log.append(("prefill", req.rid, len(sv._ahead)))
        return prefill(slot, req, *a, **kw)

    sv._exec.decode, sv._prefill = logged_decode, logged_prefill
    return log


@greedy_and_sampled
def test_open_loop_arrivals_between_ticks_keep_the_plain_streams(
        engine, sampling, monkeypatch):
    """``run()`` over arrivals that fall due between ticks, with slots free
    throughout: the plain loop's streams, a tick in flight for most ticks,
    none dropped, and every prefill launched with no tick in flight."""
    from deepspeed_tpu.inference import serving as serving_mod

    def schedule():
        reqs = _requests(7, seed=4, sampling=sampling)
        for i, r in enumerate(reqs):
            r.arrival_time = i * 2.3e-3      # a tick is ~1 ms of this clock
            r.max_new_tokens = max(r.max_new_tokens, 6)
        return reqs

    want = _streams(_serving(engine, False, b_slots=8).run(schedule()))
    monkeypatch.setattr(serving_mod, "time", _Clock())
    sv = _serving(engine, True, b_slots=8)
    log = _launch_log(sv)
    got = _streams(sv.run(schedule()))
    assert got == want and len(got) == 7
    assert sv.lookahead_dropped == 0 and not sv._ahead
    assert sv.lookahead_launched > sv._tick // 2
    fills = [i for i, e in enumerate(log) if e[0] == "prefill"]
    assert len(fills) == 7 and all(log[i][2] == 0 for i in fills)
    # while an arrival was still to come no tick was launched behind two
    # in flight (the one to fetch and one ahead of it, never two ahead)
    assert {e for e in log[:fills[-1]] if e[0] == "decode"} == {
        ("decode", 0), ("decode", 1)}


def test_an_arrival_is_admitted_after_the_one_tick_in_flight(engine):
    """A request that falls due while a tick is in flight: that tick is
    taken and emitted, nothing is launched over the arrival, and its
    prefill is the next program launched."""
    sv = _serving(engine, True)
    first, late = _requests(2)
    first.max_new_tokens, late.arrival_time = 20, 5.0
    sv.submit(first)
    sv.submit(late)
    for _ in range(3):
        sv.step(now=0.0)
    assert len(sv._ahead) == 1
    in_flight, emitted = sv._ahead[0].seq, len(sv._slots[0].tokens)
    log = _launch_log(sv)
    sv.step(now=6.0)                         # the arrival is due now
    # the tick in flight was the one emitted; the prefill followed it with
    # nothing in flight, and no decode program was launched in between
    assert sv._tok_src[0] == in_flight
    assert len(sv._slots[0].tokens) == emitted + 1
    assert log == [("prefill", late.rid, 0)]
    assert int(sv._active.sum()) == 2 and not sv._ahead
    # ... and is not fetched: its first token is still the device's
    assert [f.slot for f in sv._firsts] == [1] and not sv._slots[1].tokens
    sv.step(now=6.0)    # both slots in one tick, fed on the device; one ahead
    assert log[1:] == [("decode", 0), ("decode", 1)] and len(sv._ahead) == 1
    assert not sv._firsts and len(sv._slots[1].tokens) == 2
    assert sv.prefill_fed_on_device == 2      # the first request's too
    assert sv.lookahead_dropped == 0
    while sv.step(now=6.0):
        pass
    plain = _serving(engine, False)
    first, late = _requests(2)
    first.max_new_tokens = 20
    assert _streams(sv.take_results()) == _streams(plain.run([first, late]))


@pytest.mark.parametrize("n_prompt", [20, 2], ids=[
    "prompt-over-the-stale-row", "prompt-short-of-it"])
@greedy_and_sampled
def test_a_slot_ended_under_a_tick_in_flight_hands_its_pages_on(
        engine, sampling, n_prompt):
    """A deadline ends a live slot while a tick is in flight, and the
    request that waited for its pages is admitted onto them in the same
    step, its prefill queued behind that tick: the stale tick's K/V row
    lands first and the prefill after it (over it, or short of it: then no
    read reaches the row before the slot's own decode writes it), the tick
    is taken for the slot that went on, so the streams are the plain loop's
    and every page is accounted for."""
    def run(lookahead):
        # 8 pages of 8 rows: 5 for the first request's prompt and first
        # row, 3 for the second's, none for the third until the first has
        # gone; the first's last page, which holds the row the tick in
        # flight writes (past its middle), is then the third's first
        sv = _serving(engine, lookahead, num_pages=9, prefix_cache=False)
        rng = np.random.default_rng(2)
        reqs = [Request(rid=f"r{i}", max_new_tokens=n_new, sampling=sampling,
                        input_ids=rng.integers(1, 200, (n,)).astype(np.int32))
                for i, (n, n_new) in enumerate((
                    (34, 20), (17, 7), (n_prompt, 50 - n_prompt)))]
        reqs[0].deadline_s = 100.0
        for r in reqs:
            sv.submit(r)
        for _ in range(3):
            sv.step(now=0.0)
        assert [s is not None for s in sv._slots] == [True, True, False]
        assert len(sv._queue) == 1           # a slot free, no pages for it
        in_flight = len(sv._ahead)
        # the row the tick in flight writes for the first request
        stale_page = sv._pages.table[0, (sv._lengths[0] + in_flight) // 8]
        sv.step(now=1e6)                     # the deadline, then the admission
        assert sv._slots[0].request.rid == "r2"
        assert stale_page == sv._slots[0].pages[0]
        while sv.step(now=1e6):
            pass
        return sv, in_flight, _streams(sv.take_results())

    plain, _, want = run(False)
    ahead, in_flight, got = run(True)
    assert got == want
    assert sorted(reason for _, reason in want.values()) == [
        "deadline", "length", "length"]
    assert in_flight == 1 and ahead.lookahead_dropped == 0
    assert ahead.lookahead_stale_taken == 1
    assert ahead.page_accounting()["balanced"]


def test_the_one_deep_rule_holds_outside_run(engine):
    """``submit()`` + ``step()`` with no schedule to read: one tick in
    flight while a slot is free, a submission placed after it, streams the
    plain loop's."""
    def reqs():
        out = _requests(3, seed=6)
        for r in out:
            r.max_new_tokens = max(r.max_new_tokens, 8)
        return out

    sv = _serving(engine, True, b_slots=4)
    log = _launch_log(sv)
    seen = []                                # (ticks in flight, live slots)
    for r in reqs():
        sv.submit(r)                         # lands between two ticks
        for _ in range(2):
            sv.step()
            seen.append((len(sv._ahead), int(sv._active.sum())))
    while sv.step():
        seen.append((len(sv._ahead), int(sv._active.sum())))
    assert not sv._in_run
    assert max(depth for depth, _ in seen) == 1       # a slot always free
    assert [depth for depth, _ in seen[:6]] == [1, 1, 0, 1, 0, 1]
    assert [e for e in log if e[0] == "prefill"] == [
        ("prefill", f"r{i}", 0) for i in range(3)]
    assert sv.lookahead_dropped == 0
    assert _streams(sv.take_results()) == _streams(
        _serving(engine, False, b_slots=4).run(reqs()))


def test_the_queue_of_launched_ticks_is_bounded_and_ends_with_a_slot(engine):
    """Every slot busy: the queue fills to LOOKAHEAD_TICKS.  A slot's end by
    length is a tick the host can count, and a request could take the slot
    then (outside ``run()`` anything may be submitted): one tick is launched
    past that end, under a mask without the slot, and no second; the queue
    shrinks to that one as the slot's last tick is fetched."""
    sv = _serving(engine, True)
    for r, n in zip(_requests(3), (2 * LOOKAHEAD_TICKS + 6, 40, 40)):
        r.max_new_tokens = n
        sv.submit(r)
    depths = []
    while not sv._finished_order:
        sv.step(now=0.0)
        depths.append(len(sv._ahead))
    assert max(depths) == LOOKAHEAD_TICKS
    assert depths[-LOOKAHEAD_TICKS:] == list(range(LOOKAHEAD_TICKS, 0, -1))
    (past,) = sv._ahead
    assert past.past_end and list(past.active) == [False, True, True]
    assert sv.lookahead_past_end == 1 and sv.lookahead_dropped == 0
    # that tick is the two other slots' next one, whole
    sv.step(now=0.0)
    assert sv.lookahead_stale_taken == 0 and len(sv._ahead) == 1
    # where no request can be given a slot (admission closed) the ticks past
    # an end are as many as the queue holds
    sv._draining = True
    sv.step(now=0.0)
    assert len(sv._ahead) == LOOKAHEAD_TICKS
    while sv.step(now=0.0):
        pass
    plain = _serving(engine, False)
    reqs = _requests(3)
    for r, n in zip(reqs, (2 * LOOKAHEAD_TICKS + 6, 40, 40)):
        r.max_new_tokens = n
    assert _streams(sv.take_results()) == _streams(plain.run(reqs))
    assert sv.lookahead_dropped == 0 and not sv._ahead


def test_ticks_launched_on_another_state_are_dropped(engine):
    """What changes ONE slot between two ticks (a live request runs past its
    deadline) leaves the ticks in flight the other slots' own: they are
    taken for those, none is dropped.  What changes every slot's inputs
    (the executor's weights are another tree) drops the whole queue, and the
    ticks launched in its place are what the plain loop emits."""
    def run(lookahead, swap=False):
        sv = _serving(engine, lookahead)
        reqs = _requests(3)
        reqs[0].deadline_s = 100.0
        for r in reqs:
            r.max_new_tokens = 30
            sv.submit(r)
        for _ in range(5):
            sv.step(now=0.0)
        if swap:    # the same values in another tree
            sv._exec.params = jax.tree_util.tree_map(lambda x: x,
                                                     sv._exec.params)
        while sv.step(now=1e6):
            pass
        return sv, _streams(sv.take_results())

    plain, want = run(False)
    ahead, got = run(True)
    assert got == want
    assert sorted(reason for _, reason in want.values()) == [
        "deadline", "length", "length"]
    assert ahead.lookahead_dropped == 0
    assert ahead.lookahead_stale_taken == LOOKAHEAD_TICKS
    assert ahead.page_accounting()["balanced"]
    swapped, got = run(True, swap=True)
    assert got == want
    assert swapped.lookahead_dropped == LOOKAHEAD_TICKS
    assert swapped.page_accounting()["balanced"]


def test_lookahead_keeps_span_attrs_per_tick(engine, tracer):
    """The traced tick still reports its own rows (and, for an MoE model,
    its own expert counts): one serve.decode span a tick, attrs of the
    program that tick consumed."""
    sv = _serving(engine, True)
    sv.run(_requests(3))
    spans = [s for s in tracer.recorder.snapshot()
             if s.name == "serve.decode"]
    assert len(spans) == sv._tick and sv.lookahead_launched > 0
    for s in spans:
        assert s.attrs["live_rows"] > 0
        if sv._exec.moe_shape is not None:
            assert s.attrs["moe_live_rows"] == s.attrs["moe_rows"] > 0


def test_the_tracer_sees_what_was_in_flight_launched_and_dropped(engine,
                                                                 tracer):
    """Each ``serve.decode`` span says how many ticks were in flight when it
    opened (``ahead``), and the ticks launched ahead and dropped are
    ``health()``'s totals and can be read off the ``serve.launch`` spans: a
    launch with ``ahead`` >= 1 was launched ahead, one no ``serve.fetch``
    names was dropped."""
    sv = _serving(engine, True)
    reqs = _requests(3)
    reqs[0].deadline_s = 100.0
    for r in reqs:
        r.max_new_tokens = 30
        sv.submit(r)
    seen = []
    for now in (0.0,) * 5 + (1e6,) * 3:      # full, a deadline, a slot free
        sv.step(now=now)
        seen.append(len(sv._ahead))
    events = tracer.recorder.snapshot()
    spans = [s for s in events if s.name == "serve.decode"]
    # in flight as a tick's span opens = in flight as the step before ended
    assert [s.attrs["ahead"] for s in spans] == [0] + seen[:-1]
    # the ticks in flight at the deadline stay the two other slots' own
    assert seen == [LOOKAHEAD_TICKS] * 5 + [LOOKAHEAD_TICKS - 1,
                                            LOOKAHEAD_TICKS - 2,
                                            LOOKAHEAD_TICKS - 3]
    assert [s.attrs["own_slots"] for s in spans] == [3] * 5 + [2] * 3
    launches = [s.attrs for s in events if s.name == "serve.launch"
                and s.attrs["program"] == "decode"]
    fetched = {s.attrs["seq"] for s in events if s.name == "serve.fetch"}
    in_flight = {a.seq for a in sv._ahead}
    health = sv.health()
    assert {"serve.lookahead_launched": health["lookahead_launched_total"],
            "serve.lookahead_dropped": health["lookahead_dropped_total"]} == {
        "serve.lookahead_launched": sum(a["ahead"] >= 1 for a in launches),
        "serve.lookahead_dropped": sum(
            a["seq"] not in fetched | in_flight for a in launches)}
    assert sv.lookahead_dropped == 0 and sv.lookahead_stale_taken == 3


# ------------------------------------------------- a backlog with turnover

def _backlog(n=14, slots=4, new=4, seed=9):
    """``n`` requests due at once for ``slots`` slots, outputs staggered so
    that after the first fill a slot ends in every tick."""
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", arrival_time=0.0,
                    max_new_tokens=(2 + i if i < slots else new + 1),
                    input_ids=rng.integers(1, 200, (int(rng.integers(3, 20)),)
                                           ).astype(np.int32))
            for i in range(n)]


def launches_and_fetches(spans):
    """The ``serve.launch`` and ``serve.fetch`` spans in the order they
    opened."""
    return sorted((s for s in spans
                   if s.name in ("serve.launch", "serve.fetch")),
                  key=lambda s: s.t0)


def bare_fetches(events):
    """The ``seq`` of every fetch that opened with no later program launched
    behind the one it reads: a fetch between two launches, where the device
    stands still until the host has its answer and launches again."""
    launched, bare = 0, []
    for s in events:
        if s.name == "serve.launch":
            launched = max(launched, s.attrs["seq"])
        elif launched <= s.attrs["seq"]:
            bare.append(s.attrs["seq"])
    return bare


KINDS = {
    "dense": lambda: ("tiny", {}),
    "moe": lambda: ("tiny-moe", {"moe_drop_tokens": False}),
    "ring": lambda: ("mimo-v2.5", dict(
        num_layers=7, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
        window_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
        window_size=16, num_experts=16, moe_experts_held=4, moe_top_k=3,
        vocab_size=256, max_seq_len=512)),
    "latent": lambda: ("kanana-2-30b-a3b", dict(
        num_layers=4, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_heads=4, head_dim=24, v_head_dim=16,
        rotary_dim=8, kv_lora_rank=32, num_experts=16, moe_experts_held=4,
        moe_top_k=3, vocab_size=256, max_seq_len=512)),
}


@pytest.fixture(scope="module", params=sorted(KINDS))
def kind_engine(request):
    """One engine a kind of cache: K/V rows (dense, MoE with its counts
    behind the tokens), K/V of two kinds with a ring, a latent leaf; the
    state a slot is ``test_ssm_serving.py``'s."""
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    name, overrides = KINDS[request.param]()
    model = CausalLM(name, dtype=jnp.float32, attn_impl="xla", **overrides)
    return deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"},
        params=model.init_fn(jax.random.PRNGKey(3)),
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))


def test_backlog_with_a_slot_ending_every_tick_keeps_the_plain_streams(
        kind_engine, tracer):
    """Fourteen requests for four slots, one ending in every tick: the
    streams are the plain loop's token for token, nothing is dropped, every
    admission after the first fill feeds its first token to a tick on the
    device, and **no fetch stands between two launches**: when the host
    starts to wait for a program, the next one is already enqueued behind
    it (but for the run's last program)."""
    kw = dict(b_slots=4, page_size=8, max_model_len=64)
    plain = kind_engine.serving(lookahead=False, **kw)
    c0 = _compiles()
    want = _streams(plain.run(_backlog()))
    tracer.reset()
    sv = kind_engine.serving(**kw)
    c1 = _compiles()
    got = _streams(sv.run(_backlog()))
    assert got == want and len(got) == 14
    # the lane's program was warmed at init, and the tick it feeds is the
    # one decode program: the plain loop's compiles and no more
    assert _compiles() - c1 == c1 - c0
    assert sv._exec._decode_prog._cache_size() == 1
    h = sv.health()
    assert h["lookahead_dropped_total"] == 0
    assert h["prefill_fed_on_device_total"] >= 10
    assert h["lookahead_past_end_total"] >= 10
    assert h["first_tokens_in_flight"] == 0 and not sv._ahead
    assert sv.page_accounting()["balanced"]
    events = launches_and_fetches(tracer.recorder.snapshot())
    assert bare_fetches(events) == [sv._launch_seq]
    # fetched in launch order, every program once
    fetches = [s.attrs["seq"] for s in events if s.name == "serve.fetch"]
    assert fetches == sorted(set(fetches))
    assert len(fetches) == sv._launch_seq - events[0].attrs["seq"] + 1


def test_a_slot_ended_by_length_hands_its_pages_to_the_prefill_behind(engine):
    """A slot that ends by length under launched ticks (they run under a
    mask without it) frees its pages, and the request that waited for them
    is admitted onto them behind those ticks: no tick writes a row of the
    pages after the prefill, every stream is the plain loop's."""
    def run(lookahead):
        # 8 pages of 8 rows: 6 for the first request, 2 for the second, and
        # the first one's again for the third once it has gone
        sv = _serving(engine, lookahead, num_pages=9, prefix_cache=False)
        rng = np.random.default_rng(5)
        reqs = [Request(rid=f"r{i}", max_new_tokens=n_new,
                        input_ids=rng.integers(1, 200, (n,)).astype(np.int32))
                for i, (n, n_new) in enumerate(((40, 6), (3, 12), (30, 10)))]
        for r in reqs:
            sv.submit(r)
        handed = None
        while sv.step(now=0.0):
            if handed is None and "r0" in sv._finished_order:
                # the step that ended r0 admitted r2 onto its pages, behind
                # the tick launched past r0's end
                assert sv._slots[0].request.rid == "r2"
                handed = (len(sv._ahead), len(sv._firsts))
        return sv, handed, _streams(sv.take_results())

    plain, _, want = run(False)
    ahead, handed, got = run(True)
    assert got == want and handed == (1, 1)
    assert ahead.lookahead_past_end >= 1 and ahead.lookahead_dropped == 0
    assert ahead.prefill_fed_on_device >= 1
    assert ahead.page_accounting()["balanced"]


def test_no_more_than_two_prefills_in_flight_unfetched(engine):
    """The first fill of a backlog: prefill after prefill, each launched
    while the one before it runs, never a third unfetched; the slots are
    all filled by the one admission call."""
    sv = _serving(engine, True, b_slots=6, max_model_len=64)
    seen = []
    launch = sv._launch_prefill

    def logged(*a, **kw):
        seen.append(len(sv._firsts))
        return launch(*a, **kw)

    sv._launch_prefill = logged
    def reqs():
        out = _requests(6, seed=8)
        for r in out:
            r.max_new_tokens = max(r.max_new_tokens, 4)
        return out

    for r in reqs():
        sv.submit(r)
    sv.step(now=0.0)
    assert seen == [0, 1, 1, 1, 1, 1] and PREFILLS_IN_FLIGHT == 2
    assert int(sv._active.sum()) == 6 and not sv._firsts
    assert all(len(st.tokens) == 2 for st in sv._slots)
    while sv.step(now=0.0):
        pass
    assert _streams(sv.take_results()) == _streams(
        _serving(engine, False, b_slots=6).run(reqs()))


def test_one_token_requests_end_with_their_first_token(engine):
    """``max_new_tokens == 1``: no tick is launched for the slot; its one
    token is read when its turn comes and ends the request."""
    def reqs():
        out = _requests(5, seed=3)
        for r in out[1::2]:
            r.max_new_tokens = 1
        return out

    want = _streams(_serving(engine, False).run(reqs()))
    sv = _serving(engine, True)
    got = _streams(sv.run(reqs()))
    assert got == want
    assert [len(got[f"r{i}"][0]) for i in (1, 3)] == [1, 1]
    assert sv.lookahead_dropped == 0 and not sv._firsts
    alone = _serving(engine, True)
    (only,) = alone.run([reqs()[1]])
    assert list(only.output_ids) == got["r1"][0] and alone._launch_seq == 1


def _awaiting(engine, **kw):
    """An engine with one slot decoding and one admission just launched:
    its first token still on the device."""
    sv = _serving(engine, True, **kw)
    first, late = _requests(2)
    first.max_new_tokens, late.max_new_tokens = 20, 6
    late.arrival_time = 5.0
    sv.submit(first)
    sv.submit(late)
    for _ in range(3):
        sv.step(now=0.0)
    sv.step(now=6.0)
    assert [f.slot for f in sv._firsts] == [1] and not sv._slots[1].tokens
    return sv, first, late


def test_a_deadline_passes_with_the_first_token_still_on_the_device(engine):
    sv, first, late = _awaiting(engine)
    sv._slots[1].request.deadline_s = 1.0
    while sv.step(now=10.0):
        pass
    out = {r.rid: r for r in sv.take_results()}
    assert out[late.rid].finish_reason == "deadline"
    assert len(out[late.rid].output_ids) == 0
    assert len(out[late.rid].token_s) == 0
    # the other stream never noticed
    plain = _serving(engine, False)
    alone = _requests(1)[0]
    alone.max_new_tokens = 20
    assert list(out[first.rid].output_ids) == list(
        plain.run([alone])[0].output_ids)
    assert sv.health()["first_tokens_in_flight"] == 0
    assert sv.page_accounting()["balanced"] and sv.lookahead_dropped == 0


def test_drain_finishes_a_slot_that_awaits_its_first_token(engine):
    sv, first, late = _awaiting(engine)
    assert sv.health()["first_tokens_in_flight"] == 1
    assert sv.health()["active_slots"] == 2
    assert sv.drain() == []
    out = _streams(sv.take_results())
    plain = _serving(engine, False)
    a, b = _requests(2)
    a.max_new_tokens, b.max_new_tokens = 20, 6
    assert out == _streams(plain.run([a, b]))
    assert not sv._firsts and not sv._ahead


def test_update_params_refuses_while_a_first_token_is_in_flight(engine):
    sv, first, late = _awaiting(engine)
    with pytest.raises(RuntimeError, match="in flight"):
        sv.update_params(sv.params)
    sv._slots[0].request.deadline_s = sv._slots[1].request.deadline_s = 1.0
    sv.step(now=10.0)                   # both end; the prefill is unfetched
    assert not sv._active.any() and len(sv._firsts) == 1
    stats = sv.update_params(sv.params)     # settles it: the token is no one's
    assert stats["balanced"] and not sv._firsts and not sv._ahead
    assert sv.health()["weight_epoch"] == 1
    assert len(sv.take_results()) == 2
    again = _requests(1, seed=12)
    want = _streams(_serving(engine, False).run(_requests(1, seed=12)))
    assert _streams(sv.run(again)) == want


def test_the_watchdog_window_covers_a_first_tokens_fetch(engine):
    """A prefill's fetch that hangs when its turn comes is inside the
    tick's watchdog window: the report names it, and the stream goes on."""
    import time

    from deepspeed_tpu.resilience.watchdog import HangWatchdog

    hangs = []
    wd = HangWatchdog(timeout_s=600.0, on_hang=hangs.append, poll_s=0.02)
    try:
        sv, first, late = _awaiting(engine, watchdog=wd)   # compiles
        wd.timeout_s = 0.2
        fetch = sv._fetch

        def slow(out, program, seq):
            if program.startswith("prefill"):
                time.sleep(0.6)
            return fetch(out, program, seq)

        sv._fetch = slow
        sv.step(now=6.0)
        assert len(hangs) == 1 and "serve.decode tick" in hangs[0]
        assert len(sv._slots[1].tokens) == 2 and not sv._firsts
        sv._fetch = fetch
        while sv.step(now=6.0):
            pass
    finally:
        wd.stop()
    plain = _serving(engine, False)
    a, b = _requests(2)
    a.max_new_tokens, b.max_new_tokens = 20, 6
    assert _streams(sv.take_results()) == _streams(plain.run([a, b]))
