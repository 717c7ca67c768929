"""The one-pass gated-delta-rule decode step (``ops/pallas/delta_step.py``)
against ``_delta_step`` on the same inputs, in interpret mode asked for by
name; the packed leaf (``delta_pack`` heads' value columns a row) and its
inverse; and the rule that chooses between the two steps
(``delta_step_path``): the kernel for a decode tick's contiguous rows of a
float32 leaf at a shape its tile plan takes, where a program may hold a
kernel at all; ``_delta_step`` elsewhere."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.execution import MeshExecutor
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.mixers import MIXERS
from deepspeed_tpu.models.mixers import common as MX
from deepspeed_tpu.models.mixers import delta as DELTA
from deepspeed_tpu.ops.pallas.delta_step import (BLOCK_BYTES, delta_step,
                                                 head_block)

SLOTS = 3


def _cfg(**over):
    """Tiny widths whose state the tile plan takes: keys 8 wide, values 64
    (two heads a row of 128 lanes, as the published 192 makes 384)."""
    kw = dict(num_layers=4, hidden_size=64, intermediate_size=96,
              num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=256,
              linear_heads=4, linear_key_dim=8, linear_value_dim=64,
              linear_chunk=8, max_seq_len=512, dtype=jnp.float32)
    kw.update(over)
    return get_config("olmo-hybrid-7b", **kw)


def _inputs(cfg, layers, masked=(), seed=0):
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (layers * SLOTS, H, dk, dv), jnp.float32)
    q, k = (jax.random.normal(ks[i], (SLOTS, 1, H, dk), jnp.float32)
            for i in (1, 2))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (SLOTS, 1, H, dv), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(ks[4], (SLOTS, 1, H)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (SLOTS, 1, H)))
    for row in masked:      # what _delta_mixer hands a masked token
        g, beta = g.at[row].set(0.0), beta.at[row].set(0.0)
    return state, q, k, v, g, beta


CASES = {
    "first-layers-rows": dict(layer=0),
    "a-middle-layers-rows-in-a-leaf-of-three": dict(layer=1),
    "the-last-layers-rows": dict(layer=2),
    "masked-rows-keep-their-state": dict(layer=1, masked=(0, 2)),
    "fresh-rows-start-from-zeros": dict(layer=1, fresh=(1,)),
    "fresh-and-masked-together": dict(layer=2, fresh=(0,), masked=(1,)),
    "one-head-a-row": dict(layer=1, linear_value_dim=128),
    "published-widths-two-heads-a-row": dict(
        layer=1, linear_heads=6, linear_key_dim=96, linear_value_dim=192),
    "four-heads-a-row": dict(layer=1, linear_heads=8, linear_value_dim=32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_delta_step_on_the_rows_and_touches_no_other(case):
    kw = dict(CASES[case])
    layer, masked = kw.pop("layer"), kw.pop("masked", ())
    fresh = jnp.asarray(np.isin(np.arange(SLOTS), kw.pop("fresh", ())))
    cfg = _cfg(**kw)
    state, q, k, v, g, beta = _inputs(cfg, 3, masked)
    leaf = DELTA.delta_state_pack(cfg, state)
    p = DELTA.delta_pack(cfg)
    assert leaf.shape == (3 * SLOTS, cfg.linear_heads // p,
                          cfg.linear_key_dim, p * cfg.linear_value_dim)
    assert leaf.shape[-1] % 128 == 0
    assert np.array_equal(DELTA.delta_state_heads(cfg, leaf), state)
    row0 = layer * SLOTS
    before = state[row0:row0 + SLOTS]
    o_ref, s_ref = DELTA._delta_step(
        cfg, q, k, v, g, beta,
        jnp.where(fresh[:, None, None, None], 0.0, before))

    @jax.jit
    def run(leaf, row0):        # row0 traced, as inside the layer walk
        return delta_step(leaf, row0, fresh, jnp.exp(g[:, 0]), beta[:, 0],
                          q[:, 0], k[:, 0], v[:, 0], interpret=True)

    out, o = run(leaf, jnp.int32(row0))
    out = np.asarray(DELTA.delta_state_heads(cfg, out))
    # the same formula term for term: equal to float32 rounding (only the
    # order of the sums over the key axis is the implementation's)
    np.testing.assert_allclose(out[row0:row0 + SLOTS], np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref[:, 0]),
                               rtol=1e-5, atol=2e-5)
    # the other layers' rows: bit for bit what they were
    assert np.array_equal(out[:row0], np.asarray(state[:row0]))
    assert np.array_equal(out[row0 + SLOTS:], np.asarray(state[row0 + SLOTS:]))
    for row in masked:
        if not bool(fresh[row]):
            assert np.array_equal(out[row0 + row], np.asarray(before[row]))
    for row in np.flatnonzero(np.asarray(fresh)):
        # from zeros: the token's own write, k (beta v)^T
        alone = (np.asarray(k[row, 0])[:, :, None]
                 * (np.asarray(beta[row, 0])[:, None]
                    * np.asarray(v[row, 0]))[:, None, :])
        np.testing.assert_allclose(out[row0 + row], alone, rtol=1e-6,
                                   atol=1e-6)


def test_the_published_state_is_packed_without_padding():
    cfg = get_config("olmo-hybrid-7b")
    assert DELTA.delta_pack(cfg) == 2
    shapes = jax.eval_shape(lambda: CausalLM(get_config(
        "olmo-hybrid-7b", num_layers=16)).init_paged_cache(
            513, 128, dtype=jnp.bfloat16, slots=32))
    assert shapes["delta_state"].shape == (12, 32, 15, 96, 384)
    assert shapes["delta_state"].dtype == jnp.float32
    assert shapes["delta_conv"].shape == (12, 32, 3 * 11520)
    # a slot's state: 12 x 30 x 96 x 192 float32, what the equations hold
    assert np.prod(shapes["delta_state"].shape[2:]) * 4 * 12 == 26_542_080
    # five rows (ten heads) a grid step: 737 KB, four buffers inside 16 MiB
    assert head_block(15, 96, 384) == 5
    assert 5 * 96 * 384 * 4 <= BLOCK_BYTES < 15 * 96 * 384 * 4


REFUSED = {"values-not-in-whole-lanes": dict(linear_heads=3,
                                             linear_value_dim=48),
           "keys-not-in-whole-sublanes": dict(linear_key_dim=4,
                                              linear_value_dim=128)}


@pytest.mark.parametrize("shape", list(REFUSED))
def test_a_shape_the_tile_plan_refuses_keeps_delta_step(shape, monkeypatch):
    """The kernel raises, the rule never reaches it, and the executor says
    which step its tick holds."""
    cfg = _cfg(**REFUSED[shape])
    p = DELTA.delta_pack(cfg)
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    assert head_block(H // p, dk, p * dv) is None
    with pytest.raises(NotImplementedError, match="no tile plan"):
        delta_step(jnp.zeros((2, H // p, dk, p * dv)), 0,
                   jnp.zeros((2,), bool), jnp.ones((2, H)), jnp.ones((2, H)),
                   jnp.zeros((2, H, dk)), jnp.zeros((2, H, dk)),
                   jnp.zeros((2, H, dv)), interpret=True)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    assert DELTA.delta_step_path(cfg) == "plain"
    assert DELTA.delta_step_path(_cfg()) == "one_pass"
    ex = MeshExecutor(CausalLM(cfg), init_params(cfg, jax.random.PRNGKey(0)),
                      13, 8, 3, prefix_cache=False)
    info = ex.mesh_info()
    assert info["delta_step"] == "plain" and ex.state_passes == 3
    assert info["ssm_step"] is None and info["cache_kind"] == "state"


RULE = {
    "a-backend-that-is-not-a-tpu": (dict(), None, "plain"),
    "a-decode-tick-where-a-kernel-may-run": (dict(), True, "one_pass"),
    "named-rows": (dict(state_slot=jnp.zeros((1,), jnp.int32)), True,
                   "plain"),
    "a-leaf-that-is-not-float32": (dict(dtype=jnp.bfloat16), True, "plain"),
    "a-prompts-block-runs-the-chunk-form": (dict(tokens=64), True, None),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_reads_what_the_trace_can_observe(case, monkeypatch):
    kw, interpret, want = RULE[case]
    if interpret is not None:
        monkeypatch.setattr(MX, "_pallas_interpret", lambda: interpret)
    assert DELTA.delta_step_path(_cfg(), **kw) == want
    # a model with no delta layer has no such step, wherever it runs
    assert DELTA.delta_step_path(get_config("tiny")) is None
    assert DELTA.delta_step_path(get_config("falcon-h1-34b")) is None


def test_a_decode_tick_through_forward_paged_is_the_plain_ticks(monkeypatch):
    """Two ticks of ``forward_paged`` over three slots, one of them fresh
    and one idle, with the kernel in the layer walk: logits and both state
    leaves as the ``_delta_step`` program leaves them."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(2))
    model = CausalLM(cfg)
    seeded = None

    def ticks():
        nonlocal seeded
        cache = model.init_paged_cache(1 + SLOTS * 2, 8, slots=SLOTS)
        seeded = cache["delta_state"] = jax.random.normal(
            jax.random.PRNGKey(3), cache["delta_state"].shape)
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
    for leaf in MIXERS["linear"].pool_keys:
        np.testing.assert_allclose(np.asarray(cache_k[leaf]),
                                   np.asarray(cache_x[leaf]), rtol=1e-5,
                                   atol=1e-5)
    # the idle slot's rows: untouched in every layer
    assert np.array_equal(np.asarray(cache_k["delta_state"])[:, 2],
                          np.asarray(seeded)[:, 2])
