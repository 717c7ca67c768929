"""Serving resilience layer (ISSUE 3 tentpole): deadlines, load shedding,
slot quarantine, health/drain, and the ServingSupervisor warm-restart loop
with exact in-flight replay.

Every fault here fires from a seeded :class:`FaultInjector` rule at an
exact call count (or a seeded random one, drawn deterministically) — never
from real flaky infrastructure.  The acceptance invariants (ISSUE 3):

- every submitted request reaches a terminal ``RequestResult`` (completed,
  ``"deadline"``, or ``"shed"`` — none lost);
- completed outputs are token-identical to a fault-free run (greedy decode
  makes supervisor replay exact);
- page accounting balances after drain: pool pages = free + quarantined.
"""
from random import Random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.serving import (Request, ServeTimeout,
                                             ServingEngine, SlotPrefillError)
from deepspeed_tpu.inference.serving_supervisor import (RestartBudgetExhausted,
                                                        ServingSupervisor)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.monitor import InMemoryMonitor
from deepspeed_tpu.resilience import (FaultInjector, SITE_SERVE_DECODE,
                                      SITE_SERVE_PREFILL, SITE_SERVE_REPLAY,
                                      clear_injector, install_injector)


@pytest.fixture(autouse=True)
def _clean_injector():
    clear_injector()
    yield
    clear_injector()


@pytest.fixture(scope="module")
def tiny_engine():
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(3))
    return deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)


SERVE_KW = dict(b_slots=3, page_size=8, max_model_len=64)


def _stream(n, seed=0, smin=3, smax=14, new_choices=(4, 6, 8), eos=None,
            **extra):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    input_ids=rng.integers(1, 250,
                                           int(rng.integers(smin, smax))
                                           ).astype(np.int32),
                    max_new_tokens=int(rng.choice(new_choices)),
                    eos_token_id=eos, **extra)
            for i in range(n)]


def _copies(reqs):
    """Fresh Request objects (rids are single-use while live)."""
    return [Request(rid=r.rid, input_ids=r.input_ids,
                    max_new_tokens=r.max_new_tokens,
                    eos_token_id=r.eos_token_id,
                    arrival_time=r.arrival_time, deadline_s=r.deadline_s)
            for r in reqs]


@pytest.fixture(scope="module")
def reference(tiny_engine):
    """Fault-free serving outputs for the seed-1 stream — the parity oracle
    every supervised/chaos run below is checked against."""
    reqs = _stream(6, seed=1)
    serve = tiny_engine.serving(**SERVE_KW)
    return reqs, {r.rid: r.output_ids for r in serve.run(_copies(reqs))}


# ------------------------------------------------------------- deadlines

def test_deadline_expires_queued_request(tiny_engine):
    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=64)
    hog = Request(rid="hog", input_ids=np.array([1, 2, 3], np.int32),
                  max_new_tokens=6)
    doomed = Request(rid="doomed", input_ids=np.array([4, 5], np.int32),
                     max_new_tokens=4, deadline_s=0.5)
    serve.submit(hog)
    serve.submit(doomed)
    serve.step(now=0.0)        # hog takes the only slot; doomed queued
    assert serve.step(now=1.0) >= 0   # doomed expires: 1.0 > 0 + 0.5
    results = {r.rid: r for r in serve.run([])}
    assert results["hog"].finish_reason == "length"
    d = results["doomed"]
    assert d.finish_reason == "deadline"
    assert d.output_ids.size == 0
    assert d.retry_after_s is not None and d.retry_after_s > 0
    assert serve.deadline_count == 1
    assert serve.page_accounting()["balanced"]


def test_deadline_expires_inflight_request_and_frees_pages(tiny_engine):
    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=64)
    serve.submit(Request(rid="slow", input_ids=np.array([7, 8, 9], np.int32),
                         max_new_tokens=50, deadline_s=0.5))
    serve.step(now=0.0)                      # admitted, decoding
    assert serve._active.any()
    assert serve.step(now=2.0) == 0          # expired mid-flight
    (res,) = serve.take_results()
    assert res.finish_reason == "deadline"
    assert res.output_ids.size >= 1          # partial progress returned
    assert len(res.output_ids) < 50
    assert not serve._active.any()
    assert serve.page_accounting()["balanced"]
    # the freed slot serves the next request normally
    (res2,) = serve.run([Request(rid="next",
                                 input_ids=np.array([1, 2], np.int32),
                                 max_new_tokens=3)])
    assert res2.finish_reason == "length"


def test_deadline_validation(tiny_engine):
    serve = tiny_engine.serving(**SERVE_KW)
    with pytest.raises(ValueError, match="deadline_s"):
        serve.submit(Request(rid=0, input_ids=np.array([1], np.int32),
                             max_new_tokens=2, deadline_s=0.0))


# ---------------------------------------------------------- load shedding

def test_bounded_queue_sheds_with_retry_hint(tiny_engine):
    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=64,
                                max_queue=2)
    reqs = _stream(4, seed=2, new_choices=(4,))
    for r in reqs[:2]:
        serve.submit(r)                      # fill the bounded queue
    serve.submit(reqs[2])                    # backlog 2 >= max_queue: shed
    assert serve.shed_count == 1
    results = {r.rid: r for r in serve.run([])}
    shed = results[2]
    assert shed.finish_reason == "shed"
    assert shed.output_ids.size == 0
    assert shed.retry_after_s > 0
    assert results[0].finish_reason == "length"
    assert results[1].finish_reason == "length"
    # the shed rid was released with its result — resubmission now works
    (res,) = serve.run([_copies([reqs[2]])[0]])
    assert res.rid == 2 and res.finish_reason == "length"
    # retry hints track observed service time once completions exist
    assert serve._ema_service_s is not None and serve._ema_service_s > 0


def test_shed_results_flow_through_supervised_run(tiny_engine):
    sup = tiny_engine.supervised_serving(b_slots=1, page_size=8,
                                         max_model_len=64, max_queue=1)
    reqs = _stream(3, seed=3, new_choices=(4,))
    results = {r.rid: r for r in sup.run(_copies(reqs), max_ticks=500)}
    assert len(results) == 3                 # none lost
    reasons = sorted(r.finish_reason for r in results.values())
    assert reasons.count("shed") >= 1
    assert "length" in reasons


def test_counters_survive_warm_restart(tiny_engine):
    """A restart swaps in a fresh engine whose counters start at zero; the
    supervisor's health() must still report lifetime *_total numbers."""
    sup = tiny_engine.supervised_serving(b_slots=1, page_size=8,
                                         max_model_len=64, max_queue=2)
    reqs = _stream(4, seed=12, new_choices=(6,))
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=2)
    results = sup.run(_copies(reqs), max_ticks=500)
    assert sup.restarts == 1
    n_shed = sum(r.finish_reason == "shed" for r in results)
    assert n_shed >= 1                       # max_queue=2 shed the overflow
    assert sup.engine.shed_count == 0        # fresh incarnation...
    assert sup.health()["shed_total"] == n_shed   # ...lifetime preserved


# -------------------------------------------------------- slot quarantine

def test_repeated_prefill_failure_quarantines_slot(tiny_engine):
    mon = InMemoryMonitor()
    sup = tiny_engine.supervised_serving(monitor=mon, **SERVE_KW)
    inj = install_injector(FaultInjector())
    # two consecutive failures land on the same (first free) slot; the
    # engine fences it and serves the stream on the remaining fleet
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", every=1, max_fires=2)
    reqs = _stream(4, seed=4)
    results = sup.run(_copies(reqs), max_ticks=2000)
    assert sup.restarts == 0                 # pool survived: no restart
    assert len(results) == 4
    assert all(r.finish_reason == "length" for r in results)
    eng = sup.engine
    assert bool(eng._quarantined[0]) and not eng._quarantined[1:].any()
    assert len(eng._pages.quarantined) > 0
    h = sup.health()
    assert h["quarantined_slots"] == 1
    assert h["usable_slots"] == SERVE_KW["b_slots"] - 1
    # leaked pages are accounted, never recycled (referenced = index cache)
    assert h["free_pages"] + h["quarantined_pages"] + h["referenced_pages"] \
        == eng.num_pages - 1
    assert eng.page_accounting()["balanced"]
    assert mon.latest("serve/quarantined_slots") == 1.0


def test_single_prefill_failure_does_not_quarantine(tiny_engine):
    sup = tiny_engine.supervised_serving(**SERVE_KW)
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", at_call=1)
    (res,) = sup.run([Request(rid="r", input_ids=np.array([1, 2, 3], np.int32),
                              max_new_tokens=3)], max_ticks=500)
    assert res.finish_reason == "length"
    assert not sup.engine._quarantined.any()     # success reset the count
    assert int(sup.engine._slot_failures.sum()) == 0


def test_all_slots_quarantined_recovers_via_warm_restart(tiny_engine):
    sup = tiny_engine.supervised_serving(b_slots=1, page_size=8,
                                         max_model_len=64,
                                         quarantine_limit=1)
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", at_call=1)
    (res,) = sup.run([Request(rid="q", input_ids=np.array([5, 6], np.int32),
                              max_new_tokens=4)], max_ticks=500)
    # the single slot was fenced -> engine terminal -> supervisor rebuilt
    assert sup.restarts == 1
    assert "quarantined" in sup.restart_log[0]["cause"]
    assert res.finish_reason == "length"


# ------------------------------------------- supervisor: restart + replay

@pytest.mark.slow
def test_decode_fault_warm_restart_replays_token_exact(tiny_engine,
                                                       reference):
    reqs, ref = reference
    sup = tiny_engine.supervised_serving(**SERVE_KW)
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=4)
    results = sup.run(_copies(reqs), max_ticks=2000)
    assert sup.restarts == 1
    assert sup.restart_log[0]["replayed_inflight"] >= 1
    assert sup.restart_log[0]["programs_reused"] is True
    assert sorted(r.rid for r in results) == sorted(ref)
    for r in results:
        np.testing.assert_array_equal(r.output_ids, ref[r.rid])
        assert np.array_equal(r.input_ids, reqs[r.rid].input_ids)


@pytest.mark.slow
def test_replay_fault_is_retried_within_budget(tiny_engine, reference):
    reqs, ref = reference
    sup = tiny_engine.supervised_serving(**SERVE_KW)
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=3)
    # the first restart dies at the replay fault site; the retried restart
    # must not double-count already-generated prefix tokens
    inj.add(site=SITE_SERVE_REPLAY, kind="raise", at_call=1)
    results = sup.run(_copies(reqs), max_ticks=2000)
    assert sup.restarts == 2
    for r in results:
        np.testing.assert_array_equal(r.output_ids, ref[r.rid])


def test_restart_budget_exhaustion_is_terminal(tiny_engine):
    sup = tiny_engine.supervised_serving(max_restarts=2, **SERVE_KW)
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_DECODE, kind="raise", every=1, max_fires=0)
    with pytest.raises(RestartBudgetExhausted, match="budget exhausted"):
        sup.run(_stream(2, seed=6), max_ticks=2000)
    assert sup.restarts == 2
    assert len(sup.restart_log) == 2


@pytest.mark.slow
def test_serve_timeout_is_not_treated_as_a_fault(tiny_engine):
    sup = tiny_engine.supervised_serving(**SERVE_KW)
    with pytest.raises(ServeTimeout):
        sup.run(_stream(3, seed=7, new_choices=(8,)), max_ticks=1)
    assert sup.restarts == 0


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_decode_kill_at_random_tick_replays_token_exact(tiny_engine,
                                                              reference):
    """Satellite: inject a ``serve.decode`` failure at a seeded-random tick
    mid-stream; the supervisor's replayed outputs must be token-identical
    to the fault-free run for every request, with none lost."""
    reqs, ref = reference
    for seed in (11, 23, 37):
        kill_tick = Random(seed).randint(2, 8)
        inj = install_injector(FaultInjector())
        inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=kill_tick)
        sup = tiny_engine.supervised_serving(**SERVE_KW)
        try:
            results = sup.run(_copies(reqs), max_ticks=2000)
        finally:
            clear_injector()
        assert sup.restarts == 1, f"seed={seed} tick={kill_tick}"
        assert sorted(r.rid for r in results) == sorted(ref)
        for r in results:
            np.testing.assert_array_equal(
                r.output_ids, ref[r.rid],
                err_msg=f"seed={seed} kill_tick={kill_tick} rid={r.rid}")
        h = sup.health()
        assert h["free_pages"] + h["quarantined_pages"] \
            + h["referenced_pages"] == sup.engine.num_pages - 1


# ---------------------------------------------------------- health / drain

def test_health_snapshot_and_gauges(tiny_engine):
    mon = InMemoryMonitor()
    serve = tiny_engine.serving(monitor=mon, **SERVE_KW)
    serve.run(_stream(3, seed=8))
    h = serve.health()
    for key in ("tick", "pool_alive", "draining", "queue_depth",
                "active_slots", "usable_slots", "quarantined_slots",
                "free_pages", "quarantined_pages", "shed_total",
                "deadline_expired_total", "oldest_request_age_s",
                "retry_after_hint_s", "unclaimed_results"):
        assert key in h, key
    assert h["pool_alive"] is True
    assert h["queue_depth"] == 0 and h["active_slots"] == 0
    for gauge in ("serve/shed_total", "serve/deadline_expired_total",
                  "serve/quarantined_slots", "serve/quarantined_pages",
                  "serve/oldest_request_age_s"):
        assert mon.series(gauge), f"missing gauge {gauge}"
    assert mon.latest("serve/shed_total") == 0.0


@pytest.mark.slow
def test_drain_finishes_inflight_and_hands_back_queue(tiny_engine):
    serve = tiny_engine.serving(b_slots=2, page_size=8, max_model_len=64)
    reqs = _stream(5, seed=9, new_choices=(6,))
    for r in reqs:
        serve.submit(r)
    serve.step()                             # two admitted, three queued
    assert int(serve._active.sum()) == 2
    unserved = serve.drain(max_ticks=200)
    assert [r.rid for r in unserved] == [2, 3, 4]
    results = serve.take_results()
    assert sorted(r.rid for r in results) == [0, 1]
    assert all(r.finish_reason == "length" for r in results)
    assert serve.page_accounting()["balanced"]
    assert serve.health()["draining"] is True
    # admission is closed: later submissions shed (typed, not dropped)
    serve.submit(Request(rid="late", input_ids=np.array([1], np.int32),
                         max_new_tokens=2))
    (late,) = serve.take_results()
    assert late.finish_reason == "shed"
    # unserved rids were released for hand-off resubmission elsewhere
    other = tiny_engine.serving(b_slots=2, page_size=8, max_model_len=64)
    handed = {r.rid: r for r in other.run(unserved)}
    assert sorted(handed) == [2, 3, 4]


def test_run_on_draining_engine_fails_loudly(tiny_engine):
    """run() must not misread disabled admission as an admission deadlock
    (or spin on pending-only work): a draining engine with waiters tells
    the caller to drain() instead."""
    serve = tiny_engine.serving(b_slots=1, page_size=8, max_model_len=64)
    serve.submit(Request(rid="a", input_ids=np.array([1, 2], np.int32),
                         max_new_tokens=6))
    serve.submit(Request(rid="b", input_ids=np.array([3, 4], np.int32),
                         max_new_tokens=2))
    serve.step()                 # "a" takes the only slot, "b" queued
    assert serve._active.any()
    serve._draining = True
    with pytest.raises(RuntimeError, match="draining"):
        serve.run([])            # finishes "a", then must refuse, not spin
    assert [r.rid for r in serve.drain()] == ["b"]


def test_rebase_carries_remaining_deadline_budget():
    """A warm restart must not hand a request a fresh deadline window —
    only the unspent budget survives the re-anchor."""
    req = Request(rid=0, input_ids=np.array([1], np.int32),
                  max_new_tokens=2, arrival_time=0.0, deadline_s=1.0)
    rebased = ServingSupervisor._rebase(req, elapsed=0.75, t0=100.0)
    assert rebased.arrival_time == 0.0
    assert abs(rebased.deadline_s - 0.25) < 1e-9
    # the ORIGINAL arrival survives the re-anchor as the epoch stamp (and
    # a second rebase keeps the first epoch, not the second engine's clock)
    assert rebased.arrival_epoch_s == pytest.approx(100.0)
    again = ServingSupervisor._rebase(rebased, elapsed=0.1, t0=200.0)
    assert again.arrival_epoch_s == pytest.approx(100.0)
    # already expired: floored at an epsilon so the normal expiry path
    # still produces a terminal "deadline" result
    expired = ServingSupervisor._rebase(req, elapsed=5.0, t0=100.0)
    assert 0 < expired.deadline_s <= 1e-6
    # no deadline stays no deadline; pending offset spent counts from arrival
    free = Request(rid=1, input_ids=np.array([1], np.int32),
                   max_new_tokens=2, arrival_time=0.5, deadline_s=1.0)
    reb = ServingSupervisor._rebase(free, elapsed=0.7, t0=100.0)
    assert reb.deadline_s == pytest.approx(0.8)
    assert reb.arrival_epoch_s == pytest.approx(100.5)
    assert ServingSupervisor._rebase(
        Request(rid=2, input_ids=np.array([1], np.int32), max_new_tokens=2),
        elapsed=9.0, t0=0.0).deadline_s is None


@pytest.mark.slow
def test_mid_drain_fault_preserves_partial_progress(tiny_engine, reference):
    """Carried PR 3 gap (ISSUE 6 satellite): a ``serve.decode`` fault
    injected MID-drain used to hand the in-flight requests back unserved,
    discarding their generated tokens.  Now the supervisor warm-restarts,
    finishes the replayed in-flight work token-exactly (drain's contract is
    'finish in-flight work'), and hands back only the waiting queue."""
    reqs, ref = reference
    sup = tiny_engine.supervised_serving(b_slots=2, page_size=8,
                                         max_model_len=64)
    for r in _copies(reqs):
        sup.submit(r)
    sup.engine.step()                        # 2 in flight, 4 waiting
    inflight = sorted(st.request.rid for st in sup.engine._slots
                      if st is not None)
    assert len(inflight) == 2
    pre_tokens = {st.request.rid: len(st.tokens)
                  for st in sup.engine._slots if st is not None}
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=2)
    unserved = sup.drain(max_ticks=500)
    assert sup.restarts == 1
    assert sup.restart_log[0]["mid_drain"] is True
    assert sup.restart_log[0]["stashed"] == 4
    # waiting requests hand back as ORIGINALS, in order, never served
    assert [r.rid for r in unserved] == [r for r in sorted(ref)
                                         if r not in inflight]
    assert all(isinstance(r, Request) for r in unserved)
    # the in-flight pair FINISHED with partial progress preserved: their
    # stitched outputs are token-exact vs the fault-free oracle, and the
    # replay really continued (replays stamped, prefix tokens kept)
    results = {r.rid: r for r in sup.take_results()}
    assert sorted(results) == inflight
    for rid in inflight:
        np.testing.assert_array_equal(results[rid].output_ids, ref[rid])
        assert results[rid].replays == 1
        assert len(results[rid].output_ids) > pre_tokens[rid]
    assert sup.engine.page_accounting()["balanced"]


@pytest.mark.slow
def test_second_mid_drain_fault_keeps_queued_replay_progress(tiny_engine):
    """A SECOND fault mid-drain must not demote a queued in-flight-origin
    replay to 'never served': a replay re-queued on the replacement engine
    (here: its first prefill fails and quarantines the slot, so it waits
    behind one usable slot) carries already-generated tokens in its replay
    prompt — the next restart re-queues it instead of stashing it, and its
    stitched output stays token-exact."""
    # max_new=8 throughout: the replays must NOT finish at their replay
    # prefill, or the freed slot absorbs the queue and nothing is waiting
    # at the second fault
    reqs = _stream(6, seed=4, new_choices=(8,))
    ref = {r.rid: r.output_ids
           for r in tiny_engine.serving(**SERVE_KW).run(_copies(reqs))}
    sup = tiny_engine.supervised_serving(b_slots=2, page_size=8,
                                         max_model_len=64,
                                         quarantine_limit=1)
    for r in _copies(reqs):
        sup.submit(r)
    sup.engine.step()                        # 2 in flight, 4 waiting
    inflight = sorted(st.request.rid for st in sup.engine._slots
                      if st is not None)
    # NOTE: injector call counters start HERE — the pre-install step()'s
    # prefill/decode calls are not counted
    inj = install_injector(FaultInjector())
    # fault 1: kill an early drain decode tick -> restart 1 replays the
    # in-flight pair (4 waiting requests stashed)
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=2)
    # fault 2: the first replay PREFILL on the replacement engine fails ->
    # quarantine_limit=1 fences the slot, that replay re-queues, and the
    # second replay now waits behind ONE usable slot
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", at_call=1)
    # fault 3: kill the next decode tick while one replay is still QUEUED
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=3)
    unserved = sup.drain(max_ticks=500)
    assert sup.restarts == 2
    assert sup.restart_log[1]["mid_drain"] is True
    assert sup.restart_log[1]["stashed"] == 0     # nothing demoted...
    assert sup.restart_log[1]["requeued"] >= 1    # ...the replay re-queued
    # the 4 never-served requests still hand back as originals, in order
    assert [r.rid for r in unserved] == [r for r in sorted(ref)
                                         if r not in inflight]
    # BOTH in-flight requests finished token-exact across two restarts
    results = {r.rid: r for r in sup.take_results()}
    assert sorted(results) == inflight
    for rid in inflight:
        np.testing.assert_array_equal(results[rid].output_ids, ref[rid])
        assert results[rid].replays >= 1
    assert sup.engine.page_accounting()["balanced"]


@pytest.mark.slow
def test_abandoned_drain_stash_served_by_run(tiny_engine):
    """A drain abandoned mid-recovery (its ``ServeTimeout`` propagates
    before the hand-back) leaves never-served requests in the supervisor's
    drain stash; a subsequent ``run()`` must serve them instead of
    orphaning them with no terminal result."""
    reqs = _stream(6, seed=4, new_choices=(16,))
    ref = {r.rid: r.output_ids
           for r in tiny_engine.serving(**SERVE_KW).run(_copies(reqs))}
    sup = tiny_engine.supervised_serving(b_slots=2, page_size=8,
                                         max_model_len=64)
    for r in _copies(reqs):
        sup.submit(r)
    sup.engine.step()                        # 2 in flight, 4 waiting
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=2)
    # tick budget reaches the fault (decode call 2) but falls far short of
    # the replayed max_new=16 decodes, so the mid-drain recovery times out
    # AFTER the restart stashed the 4 waiting requests
    with pytest.raises(ServeTimeout):
        sup.drain(max_ticks=4)
    assert sup.restarts == 1
    assert sup.restart_log[0]["stashed"] == 4
    # the caller falls back to run(): EVERY submitted request — replayed
    # in-flight pair AND formerly-stashed queue — reaches a terminal,
    # token-exact result, and the stash is empty
    results = {r.rid: r for r in sup.run(max_ticks=500)}
    assert sorted(results) == sorted(ref)
    for rid, out in ref.items():
        np.testing.assert_array_equal(results[rid].output_ids, out)
    assert sup._drain_stash == []
    assert sup.engine.page_accounting()["balanced"]


def test_supervised_drain_returns_original_requests(tiny_engine):
    sup = tiny_engine.supervised_serving(b_slots=1, page_size=8,
                                         max_model_len=64)
    reqs = _stream(3, seed=10, new_choices=(5,))
    for r in reqs:
        sup.submit(r)
    sup.engine.step()
    unserved = sup.drain(max_ticks=200)
    assert [r.rid for r in unserved] == [1, 2]
    assert all(isinstance(r, Request) for r in unserved)
    (done,) = sup.take_results()
    assert done.rid == 0 and done.finish_reason == "length"


# --------------------------------------------- KV-page tiering (ISSUE 11)

@pytest.mark.chaos
@pytest.mark.slow
def test_warm_restart_and_recycle_carry_host_tier(tiny_engine):
    """Demoted prefix pages live in HOST buffers, so they survive the dead
    engine's pool: a warm restart (and a planned recycle()) carries them
    to the replacement, which serves promotions from the carried cache —
    token-exact, ledger balanced, nothing stranded."""
    from deepspeed_tpu.resilience.fault_injection import SITE_SERVE_DECODE

    rng = np.random.default_rng(3)
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

    # pool of 7 usable pages, 3 system prompts of ~3 pages each: serving
    # the rotation forces demote/promote cycling from the first batch
    sup = tiny_engine.supervised_serving(
        b_slots=1, page_size=8, max_model_len=40, num_pages=8,
        host_tier_pages=16)
    sup.run(stream())
    assert sup.health()["demoted_pages"] > 0

    inj = FaultInjector()
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=3)
    install_injector(inj)
    try:
        results = sup.run(stream(rid0=100), max_ticks=2000)
    finally:
        clear_injector()
    assert sup.restarts == 1
    entry = sup.restart_log[-1]
    assert entry["host_tier_entries_carried"] > 0
    for r in results:
        np.testing.assert_array_equal(r.output_ids, ref[r.rid % 100])
    acct = sup.engine.page_accounting()
    assert acct["balanced"] and acct["demoted"] == len(sup.engine._tier)

    # planned maintenance keeps the warm host cache too
    assert not sup.drain(max_ticks=500)
    demoted_before = sup.engine.page_accounting()["demoted"]
    assert demoted_before > 0
    assert sup.recycle()
    acct2 = sup.engine.page_accounting()
    assert acct2["balanced"] and acct2["demoted"] == demoted_before
    results3 = sup.run(stream(rid0=200), max_ticks=2000)
    for r in results3:
        np.testing.assert_array_equal(r.output_ids, ref[r.rid % 100])
    h = sup.health()
    assert h["promotions_total"] > 0 and h["demotions_total"] > 0
    assert sup.engine.page_accounting()["balanced"]


# ------------------------------------------------------------- serve soak

@pytest.mark.chaos
@pytest.mark.slow
def test_serve_soak_short_deterministic():
    """Tier-1 variant of ``tools/chaos_soak.py --mode serve``: one seeded
    soak round — randomized decode/prefill/replay kills + shedding — with
    the full invariant suite (terminality, parity, page accounting)."""
    import os
    import sys

    # remove the exact entry, NOT sys.path.pop(0): importing chaos_soak
    # runs its own path inserts (repo root + tests/, needed by its lazy
    # imports), and a blind pop would strip the one it just added
    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, os.pardir, "tools")
    sys.path.insert(0, tools)
    try:
        from chaos_soak import run_serve_soak
    finally:
        sys.path.remove(tools)
    stats = run_serve_soak(seed=5, n_requests=6, verbose=False)
    assert stats["terminal"] == stats["submitted"] == 6
    assert stats["faults_fired"] >= 1
    assert stats["parity_checked"] >= 1


@pytest.mark.chaos
@pytest.mark.slow
def test_serve_soak_short_deterministic_on_mesh():
    """The ISSUE 10 pinned seed: the same seeded kill/replay soak on a
    2-device mesh (model axis = 2) — every page-accounting + refcount
    invariant must hold with the pool SHARDED, and the soak's tp>1 branch
    re-asserts mesh facts + per-device pool bytes = total/2."""
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, os.pardir, "tools")
    sys.path.insert(0, tools)
    try:
        from chaos_soak import run_serve_soak
    finally:
        sys.path.remove(tools)
    stats = run_serve_soak(seed=5, n_requests=6, verbose=False, tp=2)
    assert stats["tp"] == 2
    assert stats["terminal"] == stats["submitted"] == 6
    assert stats["faults_fired"] >= 1
    assert stats["parity_checked"] >= 1


@pytest.mark.chaos
@pytest.mark.slow
def test_serve_soak_short_deterministic_tiered():
    """The ISSUE 11 pinned seed: the seeded kill/replay soak under
    KV-page tiering POOL PRESSURE (device pool shrunk to 10 pages, host
    tier of 8) — the schedule demotes AND promotes shared prefix pages
    across warm restarts, and the soak asserts the extended accounting
    invariant (demoted ledger == host buffers, folded into `balanced`),
    token exactness of promoted-prefix streams vs an UNTIERED reference,
    and that quarantine/restarts never strand a demoted page."""
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, os.pardir, "tools")
    sys.path.insert(0, tools)
    try:
        from chaos_soak import run_serve_soak
    finally:
        sys.path.remove(tools)
    stats = run_serve_soak(seed=2, n_requests=10, verbose=False,
                           host_tier_pages=8, num_pages=10,
                           require_tier_cycles=True)
    assert stats["terminal"] == stats["submitted"] == 10
    assert stats["faults_fired"] >= 1 and stats["restarts"] >= 1
    assert stats["demotions"] > 0 and stats["promotions"] > 0
    assert stats["parity_checked"] >= 1


@pytest.mark.slow
@pytest.mark.chaos
def test_serve_soak_driver_multiseed(tmp_path):
    """Long-form randomized serving soak (see tools/chaos_soak.py)."""
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, os.pardir, "tools")
    sys.path.insert(0, tools)
    try:
        from chaos_soak import run_serve_soak
    finally:
        sys.path.remove(tools)
    for seed in (20, 21, 22):
        stats = run_serve_soak(seed=seed, n_requests=8, verbose=False)
        assert stats["terminal"] == stats["submitted"]
    # tiered pool-pressure variants (ISSUE 11): the extended demote/
    # promote + ledger invariants under the same randomized kills
    for seed in (23, 24, 25):
        stats = run_serve_soak(seed=seed, n_requests=10, verbose=False,
                               host_tier_pages=8, num_pages=10)
        assert stats["terminal"] == stats["submitted"]


# ------------------------------------------------- flight recorder (ISSUE 4)

@pytest.mark.slow
def test_warm_restart_flight_dump_covers_poisoned_tick(tiny_engine,
                                                       reference):
    """Acceptance (ISSUE 4): a kill injected via $DS_TPU_FAULTS at
    ``serve.decode`` produces a flight-recorder dump whose spans cover the
    poisoned tick — the failed serve.tick/serve.decode spans carry the
    InjectedFault marker and ship through the monitor before the warm
    restart replays the stream (token parity preserved throughout)."""
    import json as _json
    import os

    from deepspeed_tpu.observability import configure_tracer

    reqs, ref = reference
    tracer = configure_tracer(enabled=True, capacity=4096)
    tracer.reset()
    mon = InMemoryMonitor()
    os.environ["DS_TPU_FAULTS"] = _json.dumps(
        [{"site": "serve.decode", "kind": "raise", "at_call": 3}])
    clear_injector()   # drop the cached env check: re-read DS_TPU_FAULTS
    try:
        sup = tiny_engine.supervised_serving(monitor=mon, **SERVE_KW)
        results = sup.run(_copies(reqs), max_ticks=2000)
        agg = tracer.aggregates()   # snapshot before the fixture reset
    finally:
        del os.environ["DS_TPU_FAULTS"]
        clear_injector()
        configure_tracer(enabled=False)
        tracer.reset()
    assert sup.restarts == 1
    # token parity with the fault-free oracle survives the replay
    by_rid = {r.rid: r for r in results}
    assert sorted(by_rid) == sorted(r.rid for r in reqs)
    for rid, res in by_rid.items():
        np.testing.assert_array_equal(res.output_ids, ref[rid])
    # replayed in-flight requests carry their replay count on the timeline,
    # and decode_ticks accumulates across incarnations (each incarnation's
    # first token is a prefill token, not a decode tick)
    assert any(r.replays == 1 for r in results)
    assert all(r.replays in (0, 1) for r in results)
    assert all(r.decode_ticks == len(r.output_ids) - 1 - r.replays
               for r in results if len(r.output_ids))
    # the dump covers the poisoned tick: the spans that unwound on the
    # injected fault are in the ring, tagged with the exception type
    dump = sup.last_flight_dump
    assert dump is not None and "FLIGHT RECORDER DUMP" in dump
    assert "serve.decode" in dump and "serve.tick" in dump
    assert "InjectedFault" in dump
    assert "'tick': 3" in dump                  # the poisoned tick itself
    # ...and it shipped through the monitor next to the serve/* gauges
    assert any(n.startswith("flight_recorder/serve.restart")
               for n, _ in mon.reports)
    # the restart itself was traced (it ran after this dump was taken, so
    # assert via the tracer's aggregates rather than the dump text)
    assert "serve.restart" in agg
    assert agg["serve.replay"][0] >= 1


def test_restart_dump_none_when_tracing_disabled(tiny_engine):
    """Warm restarts must not depend on tracing: with the tracer off the
    supervisor still restarts and last_flight_dump stays None."""
    from deepspeed_tpu.observability import get_tracer

    get_tracer().reset()
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=2)
    sup = tiny_engine.supervised_serving(**SERVE_KW)
    results = sup.run(_stream(3, seed=9), max_ticks=2000)
    assert sup.restarts == 1
    assert len(results) == 3
    assert sup.last_flight_dump is None


# ---------------------------------------------- probe / unfence (ISSUE 5)
@pytest.mark.chaos
def test_quarantined_slot_probed_and_unfenced(tiny_engine):
    """After probe_after_ticks clean ticks a fenced slot gets one canary
    prefill; success restores the slot WITH its pages, keeping the
    free + quarantined == pool invariant exact."""
    serve = tiny_engine.serving(**SERVE_KW, quarantine_limit=2,
                                probe_after_ticks=3)
    inj = install_injector(FaultInjector())
    # two raises at the same slot: the failed admission retries the queue
    # head on the same (first-free) slot, so both land on slot 0 -> fence
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", at_call=1)
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", at_call=1)
    fenced = False
    for r in _stream(5, seed=21):
        serve.submit(r)
    while True:
        try:
            if serve.step() == 0:
                break
        except SlotPrefillError as e:
            fenced = fenced or e.quarantined
    h = serve.health()
    assert fenced                            # the slot really was fenced
    assert h["quarantined_slots"] == 0       # ...and probed back into service
    assert h["quarantined_pages"] == 0
    assert h["probes_total"] >= 1 and h["unfenced_total"] == 1
    # the restored pages are free or cached by the prefix index — nothing
    # stays quarantined
    assert serve.page_accounting()["balanced"]
    assert h["free_pages"] + h["referenced_pages"] == serve.num_pages - 1
    results = serve.take_results()
    assert sorted(r.rid for r in results) == list(range(5))
    assert all(r.finish_reason in ("eos", "length") for r in results)


@pytest.mark.chaos
@pytest.mark.slow
def test_failed_probe_keeps_slot_fenced_until_a_clean_canary(tiny_engine):
    """A canary that still fails re-fences the slot and restarts the
    clean-tick clock; a later clean canary restores it.  Long prompts keep
    real prefills in the 32-bucket, so the planted broken 16-bucket
    program is hit ONLY by the one-token canary."""
    serve = tiny_engine.serving(**SERVE_KW, quarantine_limit=2,
                                probe_after_ticks=2)
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", at_call=1)  # fence slot 0
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", at_call=1)

    def broken_canary(*args, **kwargs):
        raise RuntimeError("canary boom")

    serve._exec._prefill_progs[16] = broken_canary
    for r in _stream(6, seed=22, smin=17, smax=30):
        serve.submit(r)
    fenced_again = False
    while True:
        try:
            n = serve.step()
        except SlotPrefillError:
            continue
        if serve.probe_count >= 1 and serve.unfence_count == 0:
            # the first canary failed: still fenced, clock restarted
            fenced_again = True
            assert serve.health()["quarantined_slots"] == 1
            # the next canary rebuilds clean
            serve._exec._prefill_progs.pop(16, None)
        if n == 0:
            break
    h = serve.health()
    assert fenced_again
    assert h["probes_total"] >= 2            # first canary failed, later won
    assert h["unfenced_total"] == 1
    assert h["quarantined_slots"] == 0
    assert serve.page_accounting()["balanced"]
    assert len(serve.take_results()) == 6


def test_probe_disabled_by_default_keeps_slot_fenced(tiny_engine):
    serve = tiny_engine.serving(**SERVE_KW, quarantine_limit=1)
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_PREFILL, kind="raise", at_call=1)
    for r in _stream(4, seed=23):
        serve.submit(r)
    while True:
        try:
            if serve.step() == 0:
                break
        except SlotPrefillError:
            pass
    h = serve.health()
    assert h["quarantined_slots"] == 1       # no background unfence path
    assert h["probes_total"] == 0
    assert serve.page_accounting()["balanced"]


# ------------------------------------- arrival epoch across warm restarts
def test_warm_restart_preserves_queued_age_and_service_ema(tiny_engine):
    """The replacement engine's gauges and retry hints must reference the
    TRUE arrival epoch and the observed service EMA, not its own freshly
    reset clock (ISSUE 5 satellite; was a documented ROADMAP gap)."""
    import time as _time

    sup = tiny_engine.supervised_serving(**SERVE_KW, max_restarts=3)
    # season the service-time EMA with a fault-free mini-stream
    sup.run(_stream(2, seed=24), max_ticks=500)
    ema = sup.engine._ema_service_s
    assert ema is not None
    old = sup.engine
    for r in _stream(3, seed=25):
        sup.submit(r)
    _time.sleep(0.15)                        # the requests age while queued
    sup._restart(RuntimeError("forced-for-test"))
    assert sup.engine is not old
    # EMA carried: hints from the fresh engine reflect observed service time
    assert sup.engine._ema_service_s == pytest.approx(ema)
    # queued age measured from the ORIGINAL arrival, not the restart
    h = sup.health()
    assert h["queue_depth"] == 3
    assert h["oldest_request_age_s"] >= 0.14
    results = sup.run([], max_ticks=2000)
    assert sorted(r.rid for r in results) == [0, 1, 2]
    # result stamps keep the pre-restart arrival: queueing time is visible
    assert all(r.queued_s >= 0.14 for r in results)
