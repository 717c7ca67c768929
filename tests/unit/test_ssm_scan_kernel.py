"""A prompt's selective scan as one kernel (``ops/pallas/ssm_scan.py``)
against ``_ssm_scan`` on the same inputs, in interpret mode asked for by
name, and the rule that chooses between them (``ssm_scan_path``): the kernel
for a block of more than one token of a serving program over a float32
state at a shape its tile plan takes, where a program may hold a kernel at
all; ``_ssm_scan`` elsewhere, and always in the training forward."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.execution import MeshExecutor
from deepspeed_tpu.models import (CausalLM, cross_entropy_loss, forward,
                                  get_config, init_params)
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.mixers import MIXERS, state_scan_paths
from deepspeed_tpu.models.mixers import common as MX
from deepspeed_tpu.models.mixers import ssm as SSM
from deepspeed_tpu.ops.pallas.ssm_scan import scan_block, ssm_scan

Q = 128
# the two cells' families at toy depth: Granite's one group of 64-wide
# heads (two heads a lane tile), Falcon-H1's two groups of 128-wide heads
FAMILIES = {
    "one-group-of-64-wide-heads": dict(ssm_heads=4, ssm_head_dim=64,
                                       ssm_state=128, ssm_groups=1),
    "two-groups-of-128-wide-heads": dict(ssm_heads=4, ssm_head_dim=128,
                                         ssm_state=128, ssm_groups=2),
}


def _cfg(family="one-group-of-64-wide-heads", **over):
    kw = dict(num_layers=2, hidden_size=64, intermediate_size=96,
              num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=256,
              ssm_chunk=Q, max_seq_len=1024, dtype=jnp.float32,
              **FAMILIES[family])
    kw.update(over)
    return get_config("falcon-h1-34b", **kw)


def _inputs(cfg, tokens, dtype=jnp.float32, rows=1, real=None, seed=0):
    """What ``_ssm_mixer_block`` hands the scan: ``dt`` 0 from position
    ``real`` on (masked), a state behind an earlier block."""
    H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x, Bm, Cm = (jax.random.normal(k, shape).astype(dtype) for k, shape in
                 zip(ks, ((rows, tokens, H, P), (rows, tokens, G, N),
                          (rows, tokens, G, N))))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (rows, tokens, H)) - 2.0)
    if real is not None:
        dt = jnp.where(jnp.arange(tokens)[None, :, None] < real, dt, 0.0)
    A = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.7))
    state = jax.random.normal(ks[5], (rows, H, P, N))
    return x, Bm, Cm, dt, A, state


def _kernel(cfg, *args):
    return ssm_scan(*args, chunk=cfg.ssm_chunk, interpret=True)


def _close(got, want, dtype):
    """The same formula term for term: equal to float32 rounding where the
    operands are float32 (only the order of a product's sum is the
    implementation's); where they are bfloat16 a rounded operand may land
    one step away, 2^-8 of a term among a row's hundreds."""
    tol = 2e-5 if dtype == jnp.float32 else 4e-3
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=tol * scale)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_kernel_is_ssm_scan_on_a_block_behind_a_carried_state(family, dtype):
    cfg = _cfg(family)
    # two rows, three chunks, the last one short: padded up inside
    args = _inputs(cfg, 2 * Q + 72, dtype, rows=2)
    y_ref, s_ref = SSM._ssm_scan(cfg, *args)
    y, s = _kernel(cfg, *args)
    assert y.shape == y_ref.shape and y.dtype == jnp.float32
    assert s.shape == s_ref.shape and s.dtype == jnp.float32
    _close(y, y_ref, dtype)
    _close(s, s_ref, dtype)


def test_masked_positions_behind_the_real_ones_change_no_number():
    """``dt = 0`` from position 150 on (a bucket's padding): the real
    positions' ``y`` and the state are the unpadded block's, bit for bit
    the state a chunk of padding alone leaves."""
    cfg = _cfg()
    x, Bm, Cm, dt, A, state = _inputs(cfg, 3 * Q, real=150)
    y, s = _kernel(cfg, x, Bm, Cm, dt, A, state)
    y_cut, s_cut = _kernel(cfg, x[:, :150], Bm[:, :150], Cm[:, :150],
                           dt[:, :150], A, state)
    np.testing.assert_array_equal(np.asarray(y[:, :150]), np.asarray(y_cut))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_cut))
    # and a block of padding alone leaves the state as it was
    _, kept = _kernel(cfg, x, Bm, Cm, jnp.zeros_like(dt), A, state)
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(state))


def test_a_state_carried_over_two_calls_is_one_call_over_both_blocks():
    cfg = _cfg("two-groups-of-128-wide-heads")
    x, Bm, Cm, dt, A, state = _inputs(cfg, 3 * Q)
    y, s = _kernel(cfg, x, Bm, Cm, dt, A, state)
    cut = Q                                     # a chunk's edge
    y0, s0 = _kernel(cfg, x[:, :cut], Bm[:, :cut], Cm[:, :cut], dt[:, :cut],
                     A, state)
    y1, s1 = _kernel(cfg, x[:, cut:], Bm[:, cut:], Cm[:, cut:], dt[:, cut:],
                     A, s0)
    np.testing.assert_array_equal(np.asarray(jnp.concatenate([y0, y1], 1)),
                                  np.asarray(y))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s))


REFUSED = {
    "state-not-in-whole-lanes": dict(ssm_state=16),
    "chunk-not-in-whole-lanes": dict(ssm_chunk=8),
    "a-head-that-is-no-whole-fraction-of-a-lane-tile": dict(ssm_head_dim=48),
    "a-group-of-one-64-wide-head": dict(ssm_heads=4, ssm_groups=4),
}


@pytest.mark.parametrize("shape", list(REFUSED))
def test_a_shape_the_tile_plan_refuses_keeps_ssm_scan(shape, monkeypatch):
    """The kernel raises, the rule never reaches it, and the executor says
    which scan its prompts hold."""
    cfg = _cfg(**REFUSED[shape])
    assert scan_block(cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim,
                      cfg.ssm_state, cfg.ssm_chunk) is None
    with pytest.raises(NotImplementedError, match="no tile plan"):
        _kernel(cfg, *_inputs(cfg, 16))
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    assert SSM.ssm_scan_path(cfg, 64) == "xla"
    assert SSM.ssm_scan_path(_cfg(), 64) == "kernel"
    if shape == "state-not-in-whole-lanes":
        ex = MeshExecutor(CausalLM(cfg), init_params(
            cfg, jax.random.PRNGKey(0)), 13, 8, 3, prefix_cache=False)
        assert ex.mesh_info()["ssm_scan"] == "xla"
        assert ex.layout.prefill_attrs(64, 40, 0)["ssm_scan"] == "xla"


def test_the_published_shapes_are_taken():
    """Granite 4.0-H (128 heads of 64 in one group, chunks of 256) and
    Falcon-H1 (32 heads of 128 in two groups, chunks of 128): blocks of
    eight heads, whole lane tiles inside one group."""
    assert scan_block(128, 1, 64, 128, 256) == 8
    assert scan_block(32, 2, 128, 256, 128) == 8


RULE = {
    "a-backend-that-is-not-a-tpu": (dict(tokens=64), None, "xla"),
    "a-prompts-block-where-a-kernel-may-run": (dict(tokens=64), True,
                                               "kernel"),
    "a-block-of-two-tokens": (dict(tokens=2), True, "kernel"),
    "one-token-a-row-runs-the-step": (dict(tokens=1), True, None),
    "a-state-that-is-not-float32": (dict(tokens=64, dtype=jnp.bfloat16),
                                    True, "xla"),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_reads_what_the_trace_can_observe(case, monkeypatch):
    kw, interpret, want = RULE[case]
    if interpret is not None:
        monkeypatch.setattr(MX, "_pallas_interpret", lambda: interpret)
    assert SSM.ssm_scan_path(_cfg(), **kw) == want
    # a model with no state a slot has no scan, wherever it runs, and the
    # table reports the key all the same
    assert SSM.ssm_scan_path(get_config("tiny"), 64) is None
    assert state_scan_paths(get_config("tiny")) == {"ssm_scan": None}


def test_a_sharded_mesh_keeps_ssm_scan(monkeypatch):
    """``pallas_call`` has no partitioning rule: the rule asks the mesh."""
    from deepspeed_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", None)
    assert SSM.ssm_scan_path(_cfg(), 64) == "kernel"
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH",
                        mesh_mod.build_mesh(mesh_mod.MeshLayout(dp=2),
                                            jax.devices()[:2]))
    assert SSM.ssm_scan_path(_cfg(), 64) == "xla"


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_prompt_in_pieces_through_forward_paged_is_the_xla_prompts(
        family, monkeypatch):
    """A 300-token prompt in a 384-token block of ``forward_paged`` with the
    mixer taking 128 positions at a time (``SSM_BLOCK_TOKENS``: three
    pieces, the last all padding behind 44 real tokens): a kernel call a
    piece with the state carried between them leaves the logits and both
    state leaves as the ``_ssm_scan`` program does."""
    monkeypatch.setattr(SSM, "SSM_BLOCK_TOKENS", Q)
    cfg = _cfg(family)
    params = init_params(cfg, jax.random.PRNGKey(2))
    model = CausalLM(cfg)
    block, real, page = 3 * Q, 300, 128
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, block), 0, 256)

    def prompt():
        cache = model.init_paged_cache(1 + block // page, page, slots=3)
        table = jnp.arange(1, 1 + block // page, dtype=jnp.int32)[None]
        # a fresh function a call: jax caches a trace by function
        def prog(c):
            return T.forward_paged(
                cfg, params, tokens, c, table, jnp.zeros((1,), jnp.int32),
                (jnp.arange(block) < real)[None],
                state_slot=jnp.ones((1,), jnp.int32),
                logits_at=jnp.full((1,), real - 1, jnp.int32))
        return jax.jit(prog)(cache), str(jax.make_jaxpr(prog)(cache))

    (want, cache_x), jaxpr_x = prompt()
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    (got, cache_k), jaxpr_k = prompt()
    assert "pallas_call" in jaxpr_k and "pallas_call" not in jaxpr_x
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    for leaf in MIXERS["ssm"].pool_keys:
        np.testing.assert_allclose(np.asarray(cache_k[leaf]),
                                   np.asarray(cache_x[leaf]), rtol=1e-5,
                                   atol=1e-5)
    # the other slots' rows: untouched in every layer
    assert not np.asarray(cache_k["ssm_state"])[:, (0, 2)].any()
    assert np.asarray(cache_k["ssm_state"])[:, 1].any()


def test_a_serving_engine_reports_the_scan_its_prompts_hold(monkeypatch):
    """``mesh_info()["ssm_scan"]`` and the ``ssm_scan`` attr of every
    ``serve.prefill`` span beside ``scan_chunks``: ``"xla"`` on this
    backend, ``"kernel"`` where a kernel may run, with the tokens of the
    ``"xla"`` engine."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.observability import Span, configure_tracer, get_tracer
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(5))
    mesh = initialize_mesh(MeshLayout(), devices=jax.devices()[:1])
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (140, 20)]

    def serve():
        engine = deepspeed_tpu.init_inference(
            model=CausalLM(cfg), params=params, dtype="fp32", mesh=mesh)
        sv = engine.serving(b_slots=2, page_size=128, max_model_len=256)
        configure_tracer(enabled=True)
        t0 = time.monotonic()       # the recorder keeps an earlier run's
        try:
            out = sv.run([Request(rid=str(i), input_ids=p, max_new_tokens=3)
                          for i, p in enumerate(prompts)])
            spans = [s for s in get_tracer().recorder.snapshot()
                     if isinstance(s, Span) and s.name == "serve.prefill"
                     and s.t0 >= t0]
        finally:
            configure_tracer(enabled=False)
        return ({r.rid: list(r.output_ids) for r in out}, sv._exec.mesh_info(),
                sv.health(), [s.attrs for s in spans])

    want, info, health, attrs = serve()
    assert info["ssm_scan"] == health["ssm_scan"] == "xla"
    assert len(attrs) == 2 and all(a["ssm_scan"] == "xla" and
                                   a["scan_chunks"] >= 1 for a in attrs)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    got, info, health, attrs = serve()
    assert info["ssm_scan"] == health["ssm_scan"] == "kernel"
    assert len(attrs) == 2 and all(a["ssm_scan"] == "kernel" for a in attrs)
    assert got == want


def test_the_training_forward_never_meets_the_kernel(monkeypatch):
    """``forward`` and its gradients run ``_ssm_scan`` wherever they are
    traced (a ``pallas_call`` has no derivative rule): the lowered loss and
    gradients are the same program with a kernel allowed as without."""
    cfg = _cfg(num_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 2 * Q), 0, 256)

    def lowered():
        def loss(p):
            return cross_entropy_loss(forward(cfg, p, tokens), tokens)
        return jax.jit(jax.value_and_grad(loss)).lower(params)

    want = lowered().as_text()
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    low = lowered()
    assert low.as_text() == want
    value, grads = low.compile()(params)
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))
    assert float(jnp.abs(grads["layers"]["ssm_in"]).max()) > 0
