"""A model with state-space layers beside attention (Falcon-H1,
``falcon_h1``: a Mamba-2 mixer and grouped-query attention on one normed
input in every block, fixed multipliers) through the model and the serving
engine: the mixer's two forms against each other and against the plain
reference, the cache's two leaves with no page axis, admission's reset, the
decode lookahead that never un-launches a tick for a slot that goes on and
feeds an admission's first token to the next tick on the device, the span
attrs, and the mechanisms that refuse such a model by name."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark.lib import reference_falcon_h1 as R
from deepspeed_tpu.inference.execution import MeshExecutor
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.mixers import common as MX
from deepspeed_tpu.models.mixers import ssm as SSM

from .test_serving_lookahead import bare_fetches, launches_and_fetches

SERVE_KW = dict(b_slots=3, page_size=8, max_model_len=96)


def tiny(**over):
    """Tiny widths, every multiplier away from 1, 2 groups, chunk 8."""
    kw = dict(num_layers=2, hidden_size=64, intermediate_size=96,
              num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=256,
              ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
              ssm_chunk=8, max_seq_len=512, embed_multiplier=3.0,
              lm_head_multiplier=0.5, attn_in_multiplier=1.5,
              attn_out_multiplier=0.7, key_multiplier=0.3,
              ssm_in_multiplier=0.8, ssm_out_multiplier=1.3,
              ssm_multipliers=(0.9, 1.2, 0.8, 1.1, 0.7),
              mlp_multipliers=(1.4, 0.6), dtype=jnp.float32)
    kw.update(over)
    return get_config("falcon-h1-34b", **kw)


@pytest.fixture(scope="module")
def params():
    return init_params(tiny(), jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def engine(params):
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    return deepspeed_tpu.init_inference(
        model=CausalLM(tiny()), params=params, dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, (1, n)),
                       jnp.int32)


@functools.partial(jax.jit, static_argnums=0)
def _forward(cfg, params, toks):
    return T.forward(cfg, params, toks)


def _is_greedy(cfg, params, prompt, out) -> bool:
    """Every token of ``out`` is the largest logit of ``forward`` over what
    came before it (one teacher-forced pass)."""
    seq = jnp.asarray(list(prompt) + list(out), jnp.int32)[None]
    logits = _forward(cfg, params, seq)[0, len(prompt) - 1:-1]
    return list(np.asarray(jnp.argmax(logits, -1))) == list(out)


def test_the_named_base_is_the_published_model_and_counts_its_parameters():
    cfg = get_config("falcon-h1-34b")
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size, cfg.num_heads,
            cfg.kv_heads, cfg.dims_per_head, cfg.intermediate_size) == (
        5120, 72, 261120, 20, 4, 128, 21504)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (32, 128, 256, 2, 4, 128)
    assert SSM.ssm_widths(cfg) == (4096, 5120, 512)
    assert SSM.ssm_in_width(cfg) == 9248
    one = (get_config(cfg, num_layers=1).param_count
           - get_config(cfg, num_layers=0).param_count)
    assert one == 430_120_032
    t = tiny()
    leaves = jax.eval_shape(lambda: init_params(t, jax.random.PRNGKey(0)))
    assert t.param_count == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(leaves))
    assert leaves["layers"]["ssm_in"].shape == (2, 64, 2 * 32 + 2 * 32 + 4)
    assert leaves["layers"]["ssm_conv_w"].shape == (2, 4, 32 + 2 * 32)


def test_forward_is_the_reference(params):
    cfg, toks = tiny(), _tokens(29)
    want = R.reference_logits(cfg, params, toks[0])
    assert R.rel_err(T.forward(cfg, params, toks)[0], want) < 1e-5


def test_paged_prefill_then_decode_is_the_reference(params):
    """The benchmark's own call: one row, ``slots`` left at its default, a
    padded prompt at start 0 and then teacher-forced single tokens."""
    from benchmark.traffic_kinds.serve_backlog import parity_paged

    class F32Cache(CausalLM):       # the harness asks for a bfloat16 pool
        def init_paged_cache(self, *a, dtype=None, **kw):
            return super().init_paged_cache(*a, dtype=jnp.float32, **kw)

    err = parity_paged(R, F32Cache(tiny()), params, 16, 21, 8, seed=5)
    assert max(err.values()) < 1e-5, err


@pytest.mark.parametrize("length,carried", [(13, False), (29, True),
                                             (8, False)])
def test_the_chunked_scan_is_the_one_step_recurrence(length, carried):
    """Over a length that is no multiple of the chunk (8), from zeros or
    from a state carried in."""
    cfg = tiny()
    H, P, N, G = 4, 8, 16, 2
    rng = np.random.default_rng(length)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    x, Bm, Cm = draw(2, length, H, P), draw(2, length, G, N), draw(2, length, G, N)
    dt = jax.nn.softplus(draw(2, length, H))
    A = -jnp.exp(draw(H) * 0.5)
    s0 = draw(2, H, P, N) if carried else jnp.zeros((2, H, P, N))
    y, s = SSM._ssm_scan(cfg, x, Bm, Cm, dt, A, s0)
    state, ys = s0, []
    for t in range(length):
        y_t, state = SSM._ssm_step(cfg, x[:, t:t + 1], Bm[:, t:t + 1],
                                 Cm[:, t:t + 1], dt[:, t:t + 1], A, state)
        ys.append(y_t)
    np.testing.assert_allclose(y, jnp.concatenate(ys, 1), atol=2e-5)
    np.testing.assert_allclose(s, state, atol=2e-5)


@pytest.mark.parametrize("name", ["tiny", "tiny-gqa", "ssm"])
def test_one_positions_logits_are_that_row_of_all(name, params):
    """``logits_at``: the head over one position a row, for every model."""
    cfg = tiny() if name == "ssm" else get_config(name, dtype=jnp.float32)
    p = params if name == "ssm" else init_params(cfg, jax.random.PRNGKey(0))
    toks = _tokens(16, seed=4)
    cache = T.init_paged_cache(cfg, 3, 8, dtype=jnp.float32)
    args = (toks, cache, jnp.arange(1, 3, dtype=jnp.int32)[None],
            jnp.zeros((1,), jnp.int32), (jnp.arange(16) < 11)[None])
    every, _ = T.forward_paged(cfg, p, *args)
    one, _ = T.forward_paged(cfg, p, *args, logits_at=jnp.asarray([10]))
    assert one.shape == (1, 1, cfg.vocab_size)
    np.testing.assert_allclose(one[0, 0], every[0, 10], atol=1e-6)


def _requests(n, seed=0, lo=3, hi=40, new=(6, 30), **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", arrival_time=0.0,
                    max_new_tokens=int(rng.integers(*new)),
                    input_ids=rng.integers(0, 256, (int(rng.integers(lo, hi)),)
                                           ).astype(np.int32), **kw)
            for i in range(n)]


def test_engine_serves_token_for_token_and_a_reused_slot_starts_clean(engine):
    """Nine requests through three slots: every slot is taken again by a
    request another just left, and each yields what greedy ``forward``
    yields, which is what it yields alone on a fresh engine."""
    cfg = engine.model.config
    reqs = _requests(9)
    sv = engine.serving(**SERVE_KW)
    assert sv._exec._pool_keys == ("k", "v", "ssm_state", "ssm_conv")
    results = {r.rid: r for r in sv.run(reqs)}
    for q in reqs:
        out = results[q.rid].output_ids
        assert len(out) == q.max_new_tokens
        assert _is_greedy(cfg, engine.params, q.input_ids, out), q.rid
    h = sv.health()
    assert sv.page_accounting()["balanced"]
    assert h["lookahead_launched_total"] > 0
    assert h["lookahead_dropped_total"] == 0
    assert h["state_pool_bytes"] == 2 * 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert h["state_pool_bytes"] < h["kv_pool_bytes_total"]
    alone = engine.serving(**SERVE_KW).run([reqs[7]])
    assert list(alone[0].output_ids) == list(results["r7"].output_ids)


def _drive(sv, requests, start=0.0):
    """Step the engine on a clock of one unit a tick (deadlines are then a
    number of ticks, the same with the lookahead and without)."""
    for q in requests:
        sv.submit(q)
    now = start
    while sv.step(now=now):
        now += 1.0
    return {r.rid: list(r.output_ids) for r in sv.take_results()}, now


def _deadline_mix():
    reqs = _requests(4, seed=7, new=(24, 30))
    reqs[0].deadline_s = 5.0        # expires while ticks are in flight
    return reqs


def test_lookahead_on_is_lookahead_off_with_a_slot_expired_in_flight(engine):
    plain, _ = _drive(engine.serving(lookahead=False, **SERVE_KW),
                      _deadline_mix())
    sv = engine.serving(**SERVE_KW)
    ahead, _ = _drive(sv, _deadline_mix())
    assert ahead == plain
    assert 0 < len(plain["r0"]) < 24
    h = sv.health()
    assert h["deadline_expired_total"] == 1
    assert h["lookahead_stale_taken_total"] > 0
    assert h["lookahead_dropped_total"] == 0
    assert sv.page_accounting()["balanced"]


def test_dropping_a_launched_tick_would_advance_the_state_twice(engine):
    """Why a launched tick is never un-launched for a slot that goes on
    (one rule for every model now): a tick that ran has advanced every live
    slot's state, so with the queue thrown away under live slots the ticks
    launched in its place advance it again and the tokens are wrong."""
    def reqs():
        return _requests(3, seed=7, new=(24, 30))

    plain, _ = _drive(engine.serving(lookahead=False, **SERVE_KW), reqs())
    sv = engine.serving(**SERVE_KW)
    for q in reqs():
        sv.submit(q)
    for now in range(5):
        sv.step(now=float(now))
    assert len(sv._ahead) > 1
    sv._ahead.clear()       # what no path of the engine does
    dropped, _ = _drive(sv, [], start=5.0)
    assert any(dropped[r] != plain[r] for r in plain)
    kept, _ = _drive(engine.serving(**SERVE_KW), reqs())
    assert kept == plain


def test_backlog_with_a_slot_ending_every_tick_keeps_the_plain_streams(engine):
    """The state model's case of ``test_serving_lookahead.py``'s: nine
    requests for three slots, one ending in every tick; a slot that ends by
    length is under no later tick's mask (a masked token changes nothing of
    its state), the request admitted behind the ticks in flight resets the
    state through its prefill and feeds its first token to the next tick on
    the device.  Token for token the plain loop's, and greedy ``forward``'s;
    no fetch stands between two launches."""
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    def reqs():
        out = _requests(9, seed=5, new=(6, 7))
        for i, q in enumerate(out):
            q.max_new_tokens = 2 + i if i < 3 else 4
        return out

    plain = {r.rid: list(r.output_ids) for r in
             engine.serving(lookahead=False, **SERVE_KW).run(reqs())}
    sv = engine.serving(**SERVE_KW)
    get_tracer().reset()    # another test's spans are not this run's
    configure_tracer(enabled=True)
    try:
        got = {r.rid: list(r.output_ids) for r in sv.run(reqs())}
        events = launches_and_fetches(get_tracer().recorder.snapshot())
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    assert got == plain
    cfg = engine.model.config
    for q in reqs():
        assert _is_greedy(cfg, engine.params, q.input_ids, got[q.rid]), q.rid
    h = sv.health()
    assert h["lookahead_dropped_total"] == 0
    assert h["lookahead_past_end_total"] >= 5
    assert h["prefill_fed_on_device_total"] >= 7
    assert h["first_tokens_in_flight"] == 0 and not sv._ahead
    assert bare_fetches(events) == [sv._launch_seq]


def test_a_deadline_passes_with_the_first_token_still_on_the_device(engine):
    """A request expires between its prefill's launch and its first token's
    fetch: its result holds no token, the tick launched behind the prefill
    is taken for the other slot, and the slot's next request starts from a
    reset state."""
    def drive(lookahead):
        sv = engine.serving(lookahead=lookahead, **SERVE_KW)
        a, b, c = _requests(3, seed=13, new=(10, 12))
        b.arrival_time, b.deadline_s = 2.0, 0.5
        c.arrival_time = 4.0
        for q in (a, b, c):
            sv.submit(q)
        sv.step(now=0.0)
        sv.step(now=1.0)
        sv.step(now=2.0)            # b is admitted behind the tick in flight
        if lookahead:
            assert [f.slot for f in sv._firsts] == [1]
        now = 3.0                   # ... and expires unfetched
        while sv.step(now=now):
            now += 1.0
        return sv, {r.rid: (list(r.output_ids), r.finish_reason)
                    for r in sv.take_results()}

    _, plain = drive(False)
    sv, ahead = drive(True)
    assert ahead["r1"] == ([], "deadline")
    assert plain["r1"][1] == "deadline"
    assert {r: ahead[r] for r in ("r0", "r2")} == {
        r: plain[r] for r in ("r0", "r2")}
    assert sv.health()["lookahead_dropped_total"] == 0
    assert sv.page_accounting()["balanced"]


def test_lookahead_on_is_lookahead_off_with_update_params_in_flight(engine,
                                                                    params):
    cfg = engine.model.config
    other = jax.tree_util.tree_map(lambda x: x * 1.25, params)

    def run(**kw):
        sv = engine.serving(**SERVE_KW, **kw)
        first = _requests(3, seed=11, new=(24, 30), deadline_s=3.0)
        out, now = _drive(sv, first)
        in_flight = len(sv._ahead)
        sv.update_params(other)
        later = [Request(rid=f"n{i}", arrival_time=now, max_new_tokens=6,
                         input_ids=q.input_ids) for i, q in enumerate(first)]
        out2, _ = _drive(sv, later, start=now)
        return sv, {**out, **out2}, in_flight, later

    _, plain, none, _ = run(lookahead=False)
    sv, ahead, in_flight, later = run()
    assert none == 0 and in_flight > 0
    assert ahead == plain
    for q in later:     # under the new weights, from a state reset
        assert len(ahead[q.rid]) == 6
        assert _is_greedy(cfg, sv.params, q.input_ids, ahead[q.rid])
    h = sv.health()
    assert h["lookahead_stale_taken_total"] >= in_flight
    assert h["lookahead_dropped_total"] == 0 and h["weight_epoch"] == 1


def test_spans_carry_the_state_and_the_scan(engine):
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)

    sv = engine.serving(**SERVE_KW)
    configure_tracer(enabled=True)
    try:
        sv.run(_requests(5, seed=3))
        spans = [s for s in get_tracer().recorder.snapshot()
                 if isinstance(s, Span)]
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    decode = [s.attrs for s in spans if s.name == "serve.decode"]
    prefill = [s.attrs for s in spans if s.name == "serve.prefill"]
    assert decode and len(prefill) == 5
    row = 2 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    for a in decode:
        assert 1 <= a["state_slots"] <= 3
        assert a["state_bytes"] == a["state_slots"] * row
        assert a["state_passes"] == 3       # the CPU's tick holds _ssm_step
    for a in prefill:
        assert a["state_reset"] == 1
        assert a["scan_chunks"] == -(-a["tokens"] // 8)
        assert a["scan_chunks_bucket"] == a["bucket"] // 8
    assert (sum(a["scan_chunks"] for a in prefill)
            < sum(a["scan_chunks_bucket"] for a in prefill))


def test_the_one_pass_step_serves_token_for_token(monkeypatch):
    """A state the kernel's tile plan takes (16 heads in 2 groups, 8 x 128)
    served with the one-pass step in the decode tick (interpret mode: the
    test answers in the backend's place, as a TPU would) against the engine
    whose tick holds ``_ssm_step``: the same tokens, greedy ``forward``'s,
    a slot taken again starts clean, and each engine's spans say how many
    passes its tick makes over the state."""
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    cfg = tiny(ssm_heads=16, ssm_state=128)
    engine = deepspeed_tpu.init_inference(
        model=CausalLM(cfg), params=init_params(cfg, jax.random.PRNGKey(1)),
        dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))

    def serve(step, passes):
        sv = engine.serving(**SERVE_KW)
        assert sv._exec.mesh_info()["ssm_step"] == step
        assert sv.health()["ssm_step"] == step
        configure_tracer(enabled=True)
        try:
            results = {r.rid: list(r.output_ids) for r in sv.run(_requests(9))}
            decode = [s.attrs for s in get_tracer().recorder.snapshot()
                      if isinstance(s, Span) and s.name == "serve.decode"]
        finally:
            configure_tracer(enabled=False)
            get_tracer().reset()
        assert decode and all(a["state_passes"] == passes for a in decode)
        assert sv.page_accounting()["balanced"]
        assert sv.health()["lookahead_dropped_total"] == 0
        return results

    plain = serve("xla", 3)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    one_pass = serve("one_pass", 2)
    assert one_pass == plain
    for q in _requests(9):
        assert len(one_pass[q.rid]) == q.max_new_tokens
        assert _is_greedy(cfg, engine.params, q.input_ids, one_pass[q.rid])
    alone = engine.serving(**SERVE_KW).run([_requests(9)[7]])
    assert list(alone[0].output_ids) == one_pass["r7"]


def test_tensor_sharded_serving_refuses():
    from deepspeed_tpu.parallel.mesh import initialize_serving_mesh

    cfg = tiny()
    with pytest.raises(NotImplementedError, match="tensor-sharded heads"):
        MeshExecutor(CausalLM(cfg), jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0))), 13, 8, 3,
            mesh=initialize_serving_mesh(tp=2), prefix_cache=False)

