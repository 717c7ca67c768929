"""``serve.launch`` / ``serve.fetch`` / ``profile.stop`` (ISSUE 34,
docs/OBSERVABILITY.md): every call that enqueues a decode or prefill program
has one ``serve.launch`` span, every blocking read of a program's output one
``serve.fetch`` that names that launch by ``seq``, the spans around them
carry what they carried, and with the tracer off the sites are the null
span and the streams are the same."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import LOOKAHEAD_TICKS, Request
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.observability import device_profiler as dp
from deepspeed_tpu.observability import trace as trace_mod
from deepspeed_tpu.observability.trace import configure_tracer

MODELS = {"dense": ("tiny", {}),
          "moe": ("tiny-moe", {"moe_drop_tokens": False})}
DECODE_ATTRS = {"tick", "slot_rids", "ahead", "own_slots", "dispatch_ms",
                "live_rows", "gathered_rows"}
PREFILL_ATTRS = {"rid", "slot", "bucket", "tokens", "shared_tokens",
                 "gathered_rows", "queued_behind"}
MOE_ATTRS = {"moe_live_rows", "moe_rows", "moe_experts_touched",
             "moe_max_load", "moe_pairs", "moe_local_pairs",
             "moe_experts_held"}


@pytest.fixture(scope="module", params=sorted(MODELS))
def engine(request):
    name, overrides = MODELS[request.param]
    model = CausalLM(name, dtype=jnp.float32, attn_impl="xla", **overrides)
    return deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"},
        params=model.init_fn(jax.random.PRNGKey(5)))


@pytest.fixture
def tracer():
    tracer = configure_tracer(enabled=True, capacity=8192)
    tracer.reset()
    yield tracer
    configure_tracer(enabled=False)
    tracer.reset()


def _requests(n=7, seed=2):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", max_new_tokens=int(rng.integers(2, 24)),
                    input_ids=rng.integers(1, 200, (int(rng.integers(3, 40)),)
                                           ).astype(np.int32))
            for i in range(n)]


def _run(engine, lookahead, log=None):
    """A short ``run()``; ``log`` receives the program of every executor
    call that enqueues a decode or prefill program, in call order."""
    sv = engine.serving(b_slots=3, page_size=8, max_model_len=64,
                        lookahead=lookahead)
    if log is not None:
        decode, prefill = sv._exec.decode, sv._exec.prefill

        def logged_decode(*a, **k):
            log.append("decode")
            return decode(*a, **k)

        def logged_prefill(s_pad, *a, **k):
            log.append(f"prefill_{s_pad}")
            return prefill(s_pad, *a, **k)

        sv._exec.decode, sv._exec.prefill = logged_decode, logged_prefill
    results = sv.run(_requests())
    return sv, {r.rid: list(r.output_ids) for r in results}


def _named(tracer, name):
    return sorted((s for s in tracer.recorder.snapshot() if s.name == name),
                  key=lambda s: s.t0)


@pytest.mark.parametrize("lookahead", [True, False],
                         ids=["lookahead", "plain"])
def test_one_launch_span_a_program_and_one_fetch_an_output(engine, tracer,
                                                           lookahead):
    log = []
    sv, _ = _run(engine, lookahead, log)
    launches, fetches = _named(tracer, "serve.launch"), _named(tracer,
                                                               "serve.fetch")
    # one span a launch, in launch order, decode and prefill in one count
    assert [s.attrs["program"] for s in launches] == log
    first = launches[0].attrs["seq"]
    assert [s.attrs["seq"] for s in launches] == list(
        range(first, first + len(log)))
    assert sv._launch_seq == launches[-1].attrs["seq"]
    assert {s.parent for s in launches} == {"serve.decode", "serve.prefill"}
    # nothing was dropped in this run: every output is fetched, once, under
    # its launch's seq and program, after that launch has returned
    assert sv.lookahead_dropped == 0
    by_seq = {s.attrs["seq"]: s for s in launches}
    assert sorted(s.attrs["seq"] for s in fetches) == sorted(by_seq)
    for f in fetches:
        launch = by_seq[f.attrs["seq"]]
        assert f.attrs["program"] == launch.attrs["program"]
        assert launch.t0 + launch.dur_s <= f.t0
        # a tick's output is read in its tick; a prefill's in its own span
        # by the plain loop, and with the lookahead when its turn in launch
        # order comes: in a tick, before an admission (``serve.tick``), or
        # in the next prefill's span (no third unfetched)
        assert f.parent in (
            {"serve.decode"} if f.attrs["program"] == "decode" else
            {"serve.prefill", "serve.decode", "serve.tick"} if lookahead
            else {"serve.prefill"})
    # ``ahead``: what the launch left in flight behind the tick being fetched
    ahead = [s.attrs["ahead"] for s in launches]
    assert sum(a >= 1 for a in ahead) == sv.lookahead_launched
    assert max(ahead) == (LOOKAHEAD_TICKS if lookahead else 0)
    assert all(s.attrs["ahead"] == 0 for s in launches
               if s.attrs["program"] != "decode")
    # ``queued_behind``: programs in flight as a prefill is launched (the
    # plain loop has fetched everything by then)
    behind = [s.attrs["queued_behind"] for s in _named(tracer,
                                                       "serve.prefill")]
    assert (max(behind) > 0) == lookahead and min(behind) == 0


def test_the_new_counters_stand_beside_the_old_on_health(engine, tracer):
    """``lookahead_past_end_total`` and ``prefill_fed_on_device_total``
    beside ``lookahead_stale_taken_total``; ``lookahead_launched_total`` and
    ``lookahead_dropped_total`` keep their names (the benchmark's note reads
    them); ``own_slots`` on a tick's span is the slots it was emitted for."""
    sv, _ = _run(engine, True)
    keys = list(sv.health())
    at = keys.index("lookahead_launched_total")
    assert keys[at:at + 6] == [
        "lookahead_launched_total", "lookahead_dropped_total",
        "lookahead_stale_taken_total", "lookahead_past_end_total",
        "prefill_fed_on_device_total", "first_tokens_in_flight"]
    h = sv.health()
    assert h["lookahead_dropped_total"] == 0 == h["first_tokens_in_flight"]
    # seven requests through three slots: the first fill's first was read
    # as the third was launched (no third unfetched), every other first
    # token reached its tick on the device; four admissions found a tick in
    # flight, launched past the end of the slot they took
    assert h["prefill_fed_on_device_total"] == 6
    assert h["lookahead_past_end_total"] >= 4
    decodes = _named(tracer, "serve.decode")
    emitted = [s.attrs["emitted"] for s in _named(tracer, "serve.emit")]
    assert [s.attrs["own_slots"] for s in decodes] == emitted
    plain, _ = _run(engine, False)
    h = plain.health()
    assert (h["lookahead_launched_total"], h["lookahead_past_end_total"],
            h["prefill_fed_on_device_total"]) == (0, 0, 0)


def test_the_spans_around_them_carry_what_they_carried(engine, tracer):
    sv, _ = _run(engine, True)
    moe = MOE_ATTRS if sv._exec.moe_shape is not None else set()
    decodes, prefills = _named(tracer, "serve.decode"), _named(
        tracer, "serve.prefill")
    assert len(decodes) == sv._tick and len(prefills) == 7
    for s in decodes:
        assert DECODE_ATTRS | moe <= set(s.attrs)
    for s in prefills:
        assert PREFILL_ATTRS | moe <= set(s.attrs)
    # dispatch_ms still ends where the launches have returned: the tick's
    # launches lie inside it, its fetch after it
    launches, fetches = _named(tracer, "serve.launch"), _named(tracer,
                                                               "serve.fetch")
    for d in decodes:
        inside = [s for s in launches if d.t0 <= s.t0 < d.t0 + d.dur_s]
        returned = d.t0 + d.attrs["dispatch_ms"] * 1e-3
        assert all(s.t0 + s.dur_s <= returned + 1e-6 for s in inside)
        mine = [f for f in fetches if d.t0 <= f.t0 < d.t0 + d.dur_s]
        # its own tick's, after the first tokens whose turn came before it
        assert [f.attrs["program"] for f in mine].count("decode") == 1
        assert mine[-1].attrs["program"] == "decode"
        assert mine[0].t0 >= returned - 1e-6


@pytest.mark.parametrize("lookahead", [True, False],
                         ids=["lookahead", "plain"])
def test_tracer_off_same_streams_and_no_span_object(engine, tracer,
                                                    monkeypatch, lookahead):
    _, traced = _run(engine, lookahead)
    assert tracer.recorder.record_count() > 0
    configure_tracer(enabled=False)
    tracer.reset()

    def no_span(*a, **k):
        raise AssertionError("a span object was built with the tracer off")

    monkeypatch.setattr(trace_mod, "_SpanCtx", no_span)
    monkeypatch.setattr(trace_mod, "_AnnotationSpan", no_span)
    sv, untraced = _run(engine, lookahead)
    assert untraced == traced
    assert tracer.recorder.record_count() == 0
    # the two sites themselves: the shared null span, nothing else
    assert trace_mod.trace_span("serve.launch", program="decode", seq=1,
                                ahead=0) is trace_mod._NULL_SPAN
    assert sv._fetch(np.int32(3), "decode", 1) == 3


@pytest.mark.parametrize("enabled", [True, False], ids=["on", "off"])
def test_profile_stop_is_a_host_span_of_its_own(tmp_path, monkeypatch,
                                                enabled):
    """The capture's stop under ``profile.stop``: recorded by the host
    tracer alone (the annotation hook is detached before it opens), and
    nothing with the tracer off."""
    import jax.profiler

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    tracer = configure_tracer(enabled=enabled, capacity=64)
    tracer.reset()
    try:
        cap = dp.DeviceTraceCapture(str(tmp_path / "xla"), n_units=1)
        with trace_mod.trace_span("serve.tick"):
            seen = cap.annotations
            cap.unit()                      # the window is spent: stop
        assert calls == ["start", "stop"] and not cap.active
        assert cap.annotations == seen      # the stop's span: not mirrored
        spans = tracer.recorder.snapshot()
        if enabled:
            (stop,) = [s for s in spans if s.name == "profile.stop"]
            assert stop.parent == "serve.tick" and stop.dur_s >= 0
        else:
            assert not spans
    finally:
        trace_mod._set_device_annotation_factory(None)
        configure_tracer(enabled=False)
        tracer.reset()
