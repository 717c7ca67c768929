"""Decode lookahead (docs/SERVING.md "Decode lookahead"): the serving loop
keeps decode ticks launched ahead of the one it fetches, each on the
device-resident tokens of the one before: up to ``LOOKAHEAD_TICKS`` where
no arrival can be placed, one where a slot is free for an arrival, none
over a request that waits.  What it must hold: the streams are the ones the
plain loop emits, it is one compiled decode program, no tick is launched
past a request's end, a tick launched on a state the host no longer holds
is never used, and an arrival's prefill is never launched behind more than
one decode program nor costs the live streams a tick."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.inference.serving import LOOKAHEAD_TICKS, Request
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
    # nothing was launched past a slot's last tick, so nothing was wasted
    assert ahead.lookahead_dropped == 0 and not ahead._ahead
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
    # while an arrival was still to come no tick was launched behind
    # another in flight: one ahead, never two
    assert {e for e in log[:fills[-1]] if e[0] == "decode"} == {("decode", 0)}


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
    in_flight, emitted = sv._ahead[0].out, len(sv._slots[0].tokens)
    log = _launch_log(sv)
    sv.step(now=6.0)                         # the arrival is due now
    # the tick in flight was the one emitted; the prefill followed it with
    # nothing in flight, and no decode program was launched in between
    assert sv._last_out[0] is in_flight
    assert len(sv._slots[0].tokens) == emitted + 1
    assert log == [("prefill", late.rid, 0)]
    assert int(sv._active.sum()) == 2 and not sv._ahead
    sv.step(now=6.0)                         # both slots: a launch, one ahead
    assert log[1:] == [("decode", 0), ("decode", 0)] and len(sv._ahead) == 1
    assert sv.lookahead_dropped == 0
    while sv.step(now=6.0):
        pass
    plain = _serving(engine, False)
    first, late = _requests(2)
    first.max_new_tokens = 20
    assert _streams(sv.take_results()) == _streams(plain.run([first, late]))


@pytest.mark.parametrize("n_prompt", [44, 12], ids=[
    "prompt-over-the-stale-row", "prompt-short-of-it"])
@greedy_and_sampled
def test_a_slot_ended_under_a_tick_in_flight_hands_its_pages_on(
        engine, sampling, n_prompt):
    """A deadline ends a live slot while a tick is in flight, and the
    request that waited for its pages is admitted onto them in the same
    step: the stale tick's K/V row lands first and the prefill after it
    (over it, or short of it: then no read reaches the row before the
    slot's own decode writes it), so the streams are the plain loop's and
    every page is accounted for."""
    def run(lookahead):
        # 8 pages of 8 rows: 7 for the first request, 1 for the second,
        # and all 7 again for the third once the first has gone
        sv = _serving(engine, lookahead, num_pages=9, prefix_cache=False)
        rng = np.random.default_rng(2)
        reqs = [Request(rid=f"r{i}", max_new_tokens=n_new, sampling=sampling,
                        input_ids=rng.integers(1, 200, (n,)).astype(np.int32))
                for i, (n, n_new) in enumerate((
                    (30, 20), (1, 7), (n_prompt, 50 - n_prompt)))]
        reqs[0].deadline_s = 100.0
        for r in reqs:
            sv.submit(r)
        for _ in range(3):
            sv.step(now=0.0)
        assert [s is not None for s in sv._slots] == [True, True, False]
        assert len(sv._queue) == 1           # a slot free, no pages for it
        in_flight = len(sv._ahead)
        # the row the tick in flight writes for the first request
        stale_page = sv._page_table[0, (sv._lengths[0] + in_flight) // 8]
        sv.step(now=1e6)                     # the deadline, then the admission
        assert sv._slots[0].request.rid == "r2"
        assert stale_page in sv._slots[0].pages
        while sv.step(now=1e6):
            pass
        return sv, in_flight, _streams(sv.take_results())

    plain, _, want = run(False)
    ahead, in_flight, got = run(True)
    assert got == want
    assert sorted(reason for _, reason in want.values()) == [
        "deadline", "length", "length"]
    assert in_flight == 1 and ahead.lookahead_dropped == 1
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
    """Every slot busy: the queue fills to LOOKAHEAD_TICKS, and shrinks to
    nothing as the first slot nears its last tick."""
    sv = _serving(engine, True)
    for r, n in zip(_requests(3), (2 * LOOKAHEAD_TICKS + 6, 40, 40)):
        r.max_new_tokens = n
        sv.submit(r)
    depths = []
    while not sv._finished_order:
        sv.step(now=0.0)
        depths.append(len(sv._ahead))
    assert max(depths) == LOOKAHEAD_TICKS
    assert depths[-LOOKAHEAD_TICKS - 1:] == list(range(LOOKAHEAD_TICKS, -1, -1))
    assert sv.lookahead_dropped == 0


def test_ticks_launched_on_another_state_are_dropped(engine):
    """Anything that changes a slot between two ticks (here: a live
    request runs past its deadline) makes the ticks launched ahead stale:
    they are dropped, and the other streams are what the plain loop emits."""
    def run(lookahead):
        sv = _serving(engine, lookahead)
        reqs = _requests(3)
        reqs[0].deadline_s = 100.0
        for r in reqs:
            r.max_new_tokens = 30
            sv.submit(r)
        for _ in range(5):
            sv.step(now=0.0)
        while sv.step(now=1e6):
            pass
        return sv, _streams(sv.take_results())

    plain, want = run(False)
    ahead, got = run(True)
    assert got == want
    assert sorted(reason for _, reason in want.values()) == [
        "deadline", "length", "length"]
    assert ahead.lookahead_dropped == LOOKAHEAD_TICKS
    assert ahead.page_accounting()["balanced"]


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
    assert seen == [LOOKAHEAD_TICKS] * 5 + [1] * 3
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
    assert sv.lookahead_dropped == LOOKAHEAD_TICKS
