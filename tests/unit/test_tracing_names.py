"""ISSUE 24: host spans where the gaps are, a stamp for every token, stable
names on the device, a capture that does not hook Python.

All CPU, all tiny: one module-scoped inference engine serves the plain,
speculative and supervised shapes; one CausalLM training engine serves the
span and scope checks of the fused step.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.inference.speculative import (SpeculativeConfig,
                                                 layer_skip_draft)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.observability import (Span, configure_tracer, get_tracer)
from deepspeed_tpu.observability import device_profiler as dp
from deepspeed_tpu.resilience import (FaultInjector, SITE_SERVE_DECODE,
                                      clear_injector, install_injector)

GEO = dict(b_slots=3, page_size=8, max_model_len=64)
LAUNCH_SPANS = ("serve.decode", "serve.prefill", "train.step")
MODEL_SCOPES = ("embed", "norm", "attn_qkv", "attn", "attn_out", "mlp",
                "lm_head")


@pytest.fixture(scope="module")
def tiny_engine():
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(3))
    return deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)


@pytest.fixture(scope="module")
def tiny_serve(tiny_engine):
    return tiny_engine.serving(**GEO)


@pytest.fixture(scope="module")
def train_engine():
    from deepspeed_tpu.parallel import mesh as mesh_mod

    from .simple_model import make_config

    mesh_mod.reset_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM("tiny", dtype=jnp.bfloat16, attn_impl="xla"),
        # bf16 over fp32 masters: the cast the ZeRO plan constrains exists
        config=make_config(batch_size=8, stage=1, precision="bf16"))
    return engine


@pytest.fixture
def traced():
    tracer = configure_tracer(enabled=True, capacity=1 << 14)
    tracer.reset()
    yield tracer
    configure_tracer(enabled=False)
    tracer.reset()


def _requests(lengths, n_new, gap_s=0.0, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"q{i}", arrival_time=i * gap_s, max_new_tokens=n_new,
                    input_ids=rng.integers(1, 250, (n,)).astype(np.int32))
            for i, n in enumerate(lengths)]


def _spans(tracer):
    return [s for s in tracer.recorder.snapshot() if isinstance(s, Span)]


def _token_batch(engine, seed):
    ids = np.random.default_rng(seed).integers(
        0, 250, (engine.train_batch_size, 16)).astype(np.int32)
    return {"input_ids": ids}


def _assert_stamps(r):
    assert r.token_s.dtype == np.float64
    assert len(r.token_s) == len(r.output_ids) > 0
    assert np.all(np.diff(r.token_s) >= 0)
    assert r.token_s[0] == r.first_token_s
    assert r.token_s[-1] <= r.finish_s
    assert r.arrival_s <= r.admit_s <= r.first_token_s


# ----------------------------------------------------------------- A: spans

class _Clock:
    """``time`` as ``inference/serving.py`` sees it: reading the clock costs
    0.1 ms and a sleep its argument, and nothing else passes."""

    def __init__(self):
        self.t = 1000.0

    def monotonic(self):
        self.t += 1e-4
        return self.t

    def sleep(self, seconds):
        self.t += seconds


def test_serving_spans_nest_beside_the_launches(tiny_serve, traced,
                                                monkeypatch):
    """The four new serving spans appear, each under the parent the issue
    states, and none of them (nor anything else but the launch and the
    fetch themselves) sits inside a span that launches a device program.

    The engine's clock is injected.  On the wall clock the test asked that
    the first request's four ticks end within the 0.25 s before the second
    falls due; as one of six xdist workers on a loaded host they did not
    always (the driver's run of PR 28's tree: the one failure of 991), and
    then ``run()`` never sleeps and no ``serve.idle`` span exists.  A read of
    this clock costs 0.1 ms however long the worker was kept waiting, so the
    gap cannot be outrun (ROADMAP D11: no assertion on the wall clock)."""
    from deepspeed_tpu.inference import serving as serving_mod

    tiny_serve.run(_requests((5, 19), 2))     # compile outside the gap
    traced.reset()
    monkeypatch.setattr(serving_mod, "time", _Clock())
    # the first request is long done when the second falls due: run() sleeps
    results = tiny_serve.run(_requests((5, 19), 4, gap_s=0.25))
    assert len(results) == 2
    spans = _spans(traced)
    parents = {}
    for s in spans:
        parents.setdefault(s.name, set()).add(s.parent)
    assert parents["serve.idle"] == {None}       # run(), between ticks
    assert parents["serve.emit"] == {"serve.tick"}
    assert parents["serve.gauges"] == {"serve.tick"}
    assert parents["serve.publish"] == {"serve.admit"}
    # ... but the launch itself and the fetch of its output (ISSUE 34)
    assert {s.name for s in spans if s.parent in LAUNCH_SPANS} == {
        "serve.launch", "serve.fetch"}
    idle = [s for s in spans if s.name == "serve.idle"]
    assert all(0 < s.attrs["wait_s"] <= 0.25 for s in idle)
    emitted = sum(s.attrs["emitted"] for s in spans if s.name == "serve.emit")
    # every token but each request's first (the prefill's) came from a tick
    assert emitted == sum(len(r.output_ids) - 1 for r in results)


def test_decode_and_prefill_attrs_match_the_requests(tiny_serve, traced):
    """live_rows / gathered_rows on serve.decode and tokens / bucket on
    serve.prefill equal what the requests imply."""
    lengths, n_new = (5, 19, 11), 4
    # prompts no other test sends: nothing to share with the prefix index
    results = tiny_serve.run(_requests(lengths, n_new, seed=24))
    assert all(r.shared_prefix_tokens == 0 for r in results)
    spans = _spans(traced)
    fills = {s.attrs["rid"]: s.attrs for s in spans
             if s.name == "serve.prefill"}
    for i, n in enumerate(lengths):
        assert fills[f"q{i}"]["tokens"] == n <= fills[f"q{i}"]["bucket"]
    ticks = [s for s in spans if s.name == "serve.decode"]
    # all three admitted in the first tick and decode in lockstep: tick j
    # (0-based) finds every slot holding its prompt plus j tokens' rows
    assert [s.attrs["live_rows"] for s in ticks] == [
        sum(lengths) + len(lengths) * j for j in range(n_new - 1)]
    # the read covers each slot's own pages up to its newest row (its
    # prompt, j tokens, the one being written), summed over the slots and
    # rounded up to whole steps of two pairs a slot of the program: not
    # slots x the longest slot's pages
    page = GEO["page_size"]

    def rows(slots, held):
        pairs = 2 * slots
        return page * pairs * -(-sum(-(-n // page) for n in held) // pairs)

    assert [s.attrs["gathered_rows"] for s in ticks] == [
        rows(GEO["b_slots"], [n + j + 1 for n in lengths])
        for j in range(n_new - 1)]
    assert ticks[0].attrs["gathered_rows"] == 48 < GEO["b_slots"] * 32
    for i, n in enumerate(lengths):
        assert fills[f"q{i}"]["gathered_rows"] == rows(1, [n])
    for s in ticks:
        assert 0 < s.attrs["dispatch_ms"] <= s.dur_s * 1e3


def test_train_spans_nest_beside_the_step(train_engine, traced):
    for seed in (0, 1):
        train_engine.train_batch(batch=_token_batch(train_engine, seed))
    spans = _spans(traced)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["train.step"]) == len(by_name["train.fetch"]) == 2
    assert len(by_name["train.monitor"]) == 4    # after launch, after fetch
    for name in ("train.data", "train.step", "train.fetch", "train.monitor"):
        assert {s.parent for s in by_name[name]} == {"train.batch"}
    assert not [s for s in spans if s.parent in LAUNCH_SPANS]
    for batch in by_name["train.batch"]:
        inside = [s for s in spans if s.parent == "train.batch"
                  and batch.t0 <= s.t0 <= batch.t0 + batch.dur_s]
        assert sum(s.dur_s for s in inside) <= batch.dur_s


# ---------------------------------------------------------------- B: stamps

def test_token_stamps_plain_and_shed(tiny_engine, tiny_serve):
    for r in tiny_serve.run(_requests((5, 19, 11, 7), 5)):
        _assert_stamps(r)
        assert r.decode_ticks == len(np.unique(r.token_s)) - 1
    bounded = tiny_engine.serving(**GEO, max_queue=1)
    reqs = _requests((5, 6, 7), 3, seed=9)
    for q in reqs:
        bounded.submit(q)
    shed = [r for r in bounded.run() if r.finish_reason == "shed"]
    assert shed and all(len(r.token_s) == 0 == len(r.output_ids)
                        for r in shed)


def test_token_stamps_speculative(tiny_engine):
    dm, dparams = layer_skip_draft(tiny_engine.model, tiny_engine.params, 1)
    spec = tiny_engine.serving(**GEO, speculative=SpeculativeConfig(
        draft_model=dm, draft_params=dparams, k=3))
    results = spec.run(_requests((5, 19), 9))
    for r in results:
        _assert_stamps(r)
        # a verify tick's 1..k tokens share its stamp: one distinct stamp
        # per decode tick, plus the prefill's
        assert len(np.unique(r.token_s)) == r.decode_ticks + 1
    assert any(r.decode_ticks < len(r.output_ids) - 1 for r in results)


def test_token_stamps_survive_a_warm_restart(tiny_engine):
    sup = tiny_engine.supervised_serving(**GEO)
    inj = FaultInjector()
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=3)
    install_injector(inj)
    try:
        results = sup.run(_requests((5, 19, 11), 8))
    finally:
        clear_injector()
    assert sup.restarts == 1
    assert sum(r.replays for r in results) > 0
    for r in results:
        _assert_stamps(r)
        if r.replays:
            # the stitched record opens with the FIRST incarnation's stamps
            events = dict((e, t) for e, t, _ in reversed(r.lifecycle))
            assert r.token_s[0] == events["first_token"]
            assert r.token_s[0] < events["replay"] < r.token_s[-1]


def test_journal_resumed_tokens_read_nan():
    from deepspeed_tpu.inference.fleet import result_from_doc, result_to_doc
    from deepspeed_tpu.inference.serving import RequestResult

    res = RequestResult(
        rid="j", input_ids=np.arange(3, dtype=np.int32),
        output_ids=np.arange(4, dtype=np.int32), finish_reason="length",
        prefill_bucket=16, resumed_tokens=2,
        token_s=np.array([np.nan, np.nan, 7.5, 7.75]))
    doc = result_to_doc(res)
    assert doc["token_s"] == [None, None, 7.5, 7.75]     # JSON has no NaN
    back = result_from_doc(doc)
    np.testing.assert_array_equal(back.token_s, res.token_s)
    # a doc from a member that predates the field: empty, not an error
    del doc["token_s"]
    assert len(result_from_doc(doc).token_s) == 0


# ------------------------------------------------------- C: names on device

def _scoped(text, scope):
    """``scope`` as one component of some op's name path in lowered text
    (under a gradient a component reads ``jvp(scope)`` or
    ``transpose(jvp(scope))``)."""
    return re.search(r'[/"(]' + re.escape(scope) + r'[/")]',
                     text) is not None


def test_serving_programs_carry_their_names_and_scopes(tiny_serve):
    tiny_serve.run(_requests((5,), 2))
    ex = tiny_serve._exec
    assert ex._decode_prog.__name__ == "serve_decode"
    (s_pad, prefill), = [(k, v) for k, v in ex._prefill_progs.items()
                         if k == 16]
    assert prefill.__name__ == f"serve_prefill_{s_pad}"
    lanes = tiny_serve._lanes_jnp()
    decode = ex._decode_prog.lower(
        ex.params, ex.pools, jnp.asarray(tiny_serve._pages.table),
        jnp.asarray(tiny_serve._lengths), jnp.asarray(tiny_serve._last_tok),
        jnp.asarray(tiny_serve._active), *lanes).as_text(debug_info=True)
    assert "module @jit_serve_decode" in decode
    one = lambda dtype: np.zeros((1,), dtype)     # noqa: E731
    fill = prefill.lower(
        ex.params, ex.pools, jnp.asarray(tiny_serve._pages.table[:1]),
        jnp.zeros((1, s_pad), jnp.int32), jnp.int32(5), jnp.int32(0),
        one(np.float32), one(np.int32), one(np.float32),
        one(np.uint32)).as_text(debug_info=True)
    assert f"module @jit_serve_prefill_{s_pad}" in fill
    for text in (decode, fill):
        missing = [s for s in MODEL_SCOPES + ("kv_write", "kv_gather",
                                              "sample")
                   if not _scoped(text, s)]
        assert not missing, missing


def test_train_step_carries_its_name_and_scopes(train_engine):
    train_engine.train_batch(batch=_token_batch(train_engine, 2))
    step = train_engine._compiled_train_step
    assert step.__name__ == "train_step"
    text = step.lower(
        train_engine.state, train_engine._collect_global_batch(
            _token_batch(train_engine, 3))).as_text(debug_info=True)
    assert "module @jit_train_step" in text
    missing = [s for s in MODEL_SCOPES + ("loss", "grad_accum", "grad_clip",
                                          "optimizer", "zero_params",
                                          "zero_grads")
               if not _scoped(text, s)]
    assert not missing, missing


# ------------------------------------------------------------ D: the capture

def test_capture_does_not_hook_python(monkeypatch, tmp_path):
    import jax.profiler

    seen = {}

    def start_trace(log_dir, **kw):
        seen["log_dir"], seen["kw"] = log_dir, kw

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    cap = dp.DeviceTraceCapture(str(tmp_path / "xla"), n_units=1)
    try:
        assert cap.active and cap.failed is None
        opts = seen["kw"]["profiler_options"]
        assert opts.python_tracer_level == 0
        # 1 is the lowest host level that still records TraceAnnotation:
        # the mirrored spans are what the capture is for
        assert opts.host_tracer_level == 1
    finally:
        cap.stop()
    assert not cap.active and get_tracer() is not None
