"""The one-pass state-space decode step (``ops/pallas/ssm_step.py``) against
``_ssm_step`` on the same inputs, in interpret mode asked for by name, and
the rule that chooses between them (``ssm_step_path``): the kernel for a
decode tick's contiguous rows of a float32 leaf at a shape its tile plan
takes, where a program may hold a kernel at all; ``_ssm_step`` elsewhere."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.execution import MeshExecutor
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.mixers import MIXERS
from deepspeed_tpu.models.mixers import common as MX
from deepspeed_tpu.models.mixers import ssm as SSM
from deepspeed_tpu.ops.pallas.ssm_step import head_block, ssm_step

SLOTS, P, N = 3, 8, 128


def _cfg(**over):
    kw = dict(num_layers=3, hidden_size=64, intermediate_size=96,
              num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=256,
              ssm_heads=16, ssm_head_dim=P, ssm_state=N, ssm_groups=2,
              ssm_chunk=8, max_seq_len=512, dtype=jnp.float32)
    kw.update(over)
    return get_config("falcon-h1-34b", **kw)


def _inputs(cfg, layers, masked=(), seed=0):
    H, G = cfg.ssm_heads, cfg.ssm_groups
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    leaf = jax.random.normal(ks[0], (layers * SLOTS, H, P, N), jnp.float32)
    x = jax.random.normal(ks[1], (SLOTS, 1, H, P), jnp.float32)
    Bm = jax.random.normal(ks[2], (SLOTS, 1, G, N), jnp.float32)
    Cm = jax.random.normal(ks[3], (SLOTS, 1, G, N), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (SLOTS, 1, H)))
    for row in masked:      # what _ssm_mixer hands a masked token
        dt = dt.at[row].set(0.0)
    A = -jnp.exp(jax.random.normal(ks[5], (H,)) * 0.5)
    return leaf, x, Bm, Cm, dt, A


CASES = {
    "first-layers-rows": dict(layer=0),
    "a-middle-layers-rows-in-a-leaf-of-three": dict(layer=1),
    "the-last-layers-rows": dict(layer=2),
    "masked-rows-keep-their-state": dict(layer=1, masked=(0, 2)),
    "fresh-rows-start-from-zeros": dict(layer=1, fresh=(1,)),
    "fresh-and-masked-together": dict(layer=2, fresh=(0,), masked=(1,)),
    "one-group": dict(layer=1, ssm_heads=8, ssm_groups=1),
    "two-groups-of-two-blocks": dict(layer=1, ssm_heads=32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_ssm_step_on_the_rows_and_touches_no_other(case):
    kw = dict(CASES[case])
    layer, masked = kw.pop("layer"), kw.pop("masked", ())
    fresh = jnp.asarray(np.isin(np.arange(SLOTS), kw.pop("fresh", ())))
    cfg = _cfg(**kw)
    leaf, x, Bm, Cm, dt, A = _inputs(cfg, 3, masked)
    row0 = layer * SLOTS
    before = leaf[row0:row0 + SLOTS]
    y_ref, s_ref = SSM._ssm_step(
        cfg, x, Bm, Cm, dt, A,
        jnp.where(fresh[:, None, None, None], 0.0, before))

    @jax.jit
    def run(leaf, row0):        # row0 traced, as inside the layer scan
        dt1 = dt[:, 0]
        return ssm_step(leaf, row0, fresh, jnp.exp(dt1 * A),
                        x[:, 0] * dt1[..., None], Bm[:, 0], Cm[:, 0],
                        interpret=True)

    out, y = run(leaf, jnp.int32(row0))
    out, y = np.asarray(out), np.asarray(y)
    # the same formula term for term: equal to float32 rounding (only the
    # order of the sum over the state's columns is the implementation's)
    np.testing.assert_allclose(out[row0:row0 + SLOTS], np.asarray(s_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, np.asarray(y_ref[:, 0]), rtol=1e-5,
                               atol=2e-5)
    # the other layers' rows: bit for bit what they were
    assert np.array_equal(out[:row0], np.asarray(leaf[:row0]))
    assert np.array_equal(out[row0 + SLOTS:], np.asarray(leaf[row0 + SLOTS:]))
    for row in masked:
        if not bool(fresh[row]):
            assert np.array_equal(out[row0 + row], np.asarray(before[row]))
    for row in np.flatnonzero(np.asarray(fresh)):
        # from zeros: what the token alone leaves
        alone = np.asarray(x[row, 0] * dt[row, 0][:, None])[..., None] * \
            np.repeat(np.asarray(Bm[row, 0]), cfg.ssm_heads
                      // cfg.ssm_groups, axis=0)[:, None, :]
        np.testing.assert_allclose(out[row0 + row], alone, rtol=1e-6,
                                   atol=1e-6)


REFUSED = {"state-not-in-whole-lanes": dict(ssm_state=16),
           "head-dim-not-in-whole-sublanes": dict(ssm_head_dim=4),
           "a-group-of-fewer-heads-than-a-block": dict(ssm_heads=4)}


@pytest.mark.parametrize("shape", list(REFUSED))
def test_a_shape_the_tile_plan_refuses_keeps_ssm_step(shape, monkeypatch):
    """The kernel raises, the rule never reaches it, and the executor says
    which step its tick holds."""
    cfg = _cfg(num_layers=2, **REFUSED[shape])
    assert head_block(cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim,
                      cfg.ssm_state) is None
    H, G, Pd, Nd = (cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim,
                    cfg.ssm_state)
    with pytest.raises(NotImplementedError, match="no tile plan"):
        ssm_step(jnp.zeros((2, H, Pd, Nd)), 0, jnp.zeros((2,), bool),
                 jnp.ones((2, H)), jnp.zeros((2, H, Pd)),
                 jnp.zeros((2, G, Nd)), jnp.zeros((2, G, Nd)),
                 interpret=True)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    assert SSM.ssm_step_path(cfg) == "xla"
    assert SSM.ssm_step_path(_cfg()) == "one_pass"
    ex = MeshExecutor(CausalLM(cfg), init_params(cfg, jax.random.PRNGKey(0)),
                      13, 8, 3, prefix_cache=False)
    assert ex.mesh_info()["ssm_step"] == "xla" and ex.state_passes == 3


RULE = {
    "a-backend-that-is-not-a-tpu": (dict(), None, "xla"),
    "a-decode-tick-where-a-kernel-may-run": (dict(), True, "one_pass"),
    "named-rows": (dict(state_slot=jnp.zeros((1,), jnp.int32)), True, "xla"),
    "a-leaf-that-is-not-float32": (dict(dtype=jnp.bfloat16), True, "xla"),
    "a-prompts-block-runs-the-scan": (dict(tokens=64), True, None),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_reads_what_the_trace_can_observe(case, monkeypatch):
    kw, interpret, want = RULE[case]
    if interpret is not None:
        monkeypatch.setattr(MX, "_pallas_interpret", lambda: interpret)
    assert SSM.ssm_step_path(_cfg(), **kw) == want
    # a model with no state a slot has no step, wherever it runs
    assert SSM.ssm_step_path(get_config("tiny")) is None


def test_a_sharded_mesh_keeps_ssm_step(monkeypatch):
    """``pallas_call`` has no partitioning rule: the rule asks the mesh."""
    from deepspeed_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", None)
    assert MX._pallas_interpret() is False
    assert SSM.ssm_step_path(_cfg()) == "one_pass"
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH",
                        mesh_mod.build_mesh(mesh_mod.MeshLayout(dp=2),
                                            jax.devices()[:2]))
    assert MX._pallas_interpret() is None
    assert SSM.ssm_step_path(_cfg()) == "xla"


def test_a_decode_tick_through_forward_paged_is_the_xla_ticks(monkeypatch):
    """Two ticks of ``forward_paged`` over three slots, one of them fresh
    and one idle, with the kernel in the layer scan: logits and both state
    leaves as the ``_ssm_step`` program leaves them."""
    cfg = _cfg(num_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(2))
    model = CausalLM(cfg)

    def ticks():
        cache = model.init_paged_cache(1 + SLOTS * 2, 8, slots=SLOTS)
        cache["ssm_state"] = jax.random.normal(
            jax.random.PRNGKey(3), cache["ssm_state"].shape)
        table = jnp.arange(1, 1 + SLOTS * 2, dtype=jnp.int32).reshape(
            SLOTS, 2)
        mask = jnp.array([[True], [True], [False]])
        step = jax.jit(lambda c, t, s: T.forward_paged(
            cfg, params, t, c, table, s, mask))
        outs = []
        for start, toks in ((jnp.array([0, 5, 7]), [[3], [4], [5]]),
                            (jnp.array([1, 6, 7]), [[6], [7], [8]])):
            logits, cache = step(cache, jnp.asarray(toks, jnp.int32),
                                 start.astype(jnp.int32))
            outs.append(np.asarray(logits))
        return outs, cache

    want, cache_x = ticks()
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    got, cache_k = ticks()
    for a, b in zip(got, want):
        np.testing.assert_allclose(a[:2], b[:2], rtol=2e-4, atol=2e-4)
    for leaf in MIXERS["ssm"].pool_keys:
        np.testing.assert_allclose(np.asarray(cache_k[leaf]),
                                   np.asarray(cache_x[leaf]), rtol=1e-5,
                                   atol=1e-5)
    # the idle slot's rows: untouched in every layer
    idle = np.asarray(cache_k["ssm_state"])[:, 2]
    assert np.array_equal(idle, np.asarray(jax.random.normal(
        jax.random.PRNGKey(3), cache_k["ssm_state"].shape))[:, 2])
