"""Decode lookahead (docs/SERVING.md "Decode lookahead"): the serving loop
keeps up to ``LOOKAHEAD_TICKS`` decode ticks launched ahead of the one it
fetches, each on the device-resident tokens of the one before.  What it
must hold: the streams are the ones the plain loop emits, it is one
compiled decode program, no tick is launched past a request's end, and a
tick launched on a state the host no longer holds is never used."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.inference.serving import LOOKAHEAD_TICKS, Request
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.utils.compile_counter import compile_counter

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


def _serving(engine, lookahead, **kw):
    return engine.serving(b_slots=3, page_size=8, max_model_len=64,
                          lookahead=lookahead, **kw)


def _requests(n=9, seed=1, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", max_new_tokens=int(rng.integers(2, 30)),
                    input_ids=rng.integers(1, 200, (int(rng.integers(3, 20)),)
                                           ).astype(np.int32), **kw)
            for i in range(n)]


def _streams(results):
    return {r.rid: (list(r.output_ids), r.finish_reason) for r in results}


@pytest.mark.parametrize("sampling", [None, SamplingParams(
    temperature=0.8, top_k=20, seed=11)], ids=["greedy", "sampled"])
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


def test_no_lookahead_while_a_request_waits_for_a_free_slot(engine):
    """With a slot free and an arrival still to come the next tick starts
    with an admission, so nothing is launched over it."""
    sv = _serving(engine, True)
    now, later = _requests(2)
    now.max_new_tokens, later.arrival_time = 12, 1e9
    sv.submit(now)
    sv.submit(later)
    for _ in range(6):
        sv.step(now=0.0)
    assert sv.lookahead_launched == 0 and not sv._ahead


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


def test_lookahead_keeps_span_attrs_per_tick(engine):
    """The traced tick still reports its own rows (and, for an MoE model,
    its own expert counts): one serve.decode span a tick, attrs of the
    program that tick consumed."""
    from deepspeed_tpu.observability.trace import configure_tracer

    tracer = configure_tracer(enabled=True, capacity=4096)
    tracer.reset()
    try:
        sv = _serving(engine, True)
        sv.run(_requests(3))
        spans = [s for s in tracer.recorder.snapshot()
                 if s.name == "serve.decode"]
    finally:
        configure_tracer(enabled=False)
    assert len(spans) == sv._tick and sv.lookahead_launched > 0
    for s in spans:
        assert s.attrs["live_rows"] > 0
        if sv._exec.moe_shape is not None:
            assert s.attrs["moe_live_rows"] == s.attrs["moe_rows"] > 0
