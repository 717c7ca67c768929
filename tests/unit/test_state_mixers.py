"""What every mixer that keeps a state a slot promises alike, said once over
the rows of ``models.mixers.MIXERS`` (ISSUE 56): the cache's leaves with no
page axis, the rows a batch reads and writes, a padded block, the mechanisms
that know pages alone, what the block is not built from, and that the
table's row is what the model file, the cache's builder and the executor
each carry.  Each kind's tiny model is its own file's (``test_ssm_serving``,
``test_delta_hybrid_serving``, ``test_conv_hybrid_serving``), where its
numbers are held to its reference."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.inference.execution import MeshExecutor
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import mixers
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.mixers import MIXERS

from . import test_conv_hybrid_serving as conv_file
from . import test_delta_hybrid_serving as delta_file
from . import test_ssm_serving as ssm_file
from .test_ssm_serving import SERVE_KW, _forward, _tokens

KINDS = list(MIXERS)
# each kind's tiny config, and the group of its layers that holds the mixer
# (None: every layer of a uniform stack)
TINY = {"ssm": (ssm_file.tiny, None),
        "linear": (delta_file.tiny, "linear_dense"),
        "conv": (conv_file.tiny, "conv_moe")}


@pytest.fixture(scope="module")
def models():
    """``get(kind) -> (cfg, params, engine)``, built at a kind's first case
    and shared by the rest."""
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    built = {}

    def get(kind):
        if kind not in built:
            cfg = TINY[kind][0]()
            params = init_params(cfg, jax.random.PRNGKey(1))
            built[kind] = (cfg, params, deepspeed_tpu.init_inference(
                model=CausalLM(cfg), params=params, dtype="fp32",
                mesh=initialize_mesh(MeshLayout(),
                                     devices=jax.devices()[:1])))
        return built[kind]
    return get


def _mixer_layer(kind, cfg, params):
    """``(the uniform config, one layer's leaves)`` of a layer with the
    kind's mixer."""
    group = TINY[kind][1]
    if group is None:
        return cfg, {k: v[0] for k, v in params["layers"].items()}
    return (T.layer_groups(cfg)[group][0],
            {k: v[0] for k, v in params["layers"][group].items()})


# ------------------------------------------------------- the table's rows

@pytest.mark.parametrize("kind", KINDS)
def test_a_row_is_what_the_model_the_cache_and_the_executor_carry(kind,
                                                                 models):
    m = MIXERS[kind]
    cfg, params, engine = models(kind)
    assert mixers.mixers_of(cfg) == (m,) and T.has_state(cfg)
    assert T.cache_kind(cfg) == ("state", m.keeps)
    # the cache: exactly the row's leaves beside K and V, a row a slot
    assert set(m.pool_keys) <= set(T.STATE_POOL_KEYS) <= set(
        T.PAGED_POOL_KEYS)
    cache = T.init_paged_cache(cfg, 7, 8, dtype=jnp.float32, slots=3)
    assert set(cache) == {"k", "v", *m.pool_keys}
    kv_layers, state_layers = T.cache_layers(cfg)
    for key in m.pool_keys:
        assert cache[key].shape[:2] == (state_layers, 3)     # no page axis
    assert cache["k"].shape[:3] == (kv_layers, 7, 8)
    assert set(T.paged_cache_specs(cfg)) == set(cache)
    # the parameters: what init_params made is what param_count says and
    # what param_specs names
    def names(tree):
        return {jax.tree_util.keystr(path) for path, _ in
                jax.tree_util.tree_leaves_with_path(tree, is_leaf=lambda s:
                                                    isinstance(s, P))}

    assert cfg.param_count == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert names(T.param_specs(cfg)) == names(params)
    made = set(jax.eval_shape(lambda: m.init(
        _mixer_layer(kind, cfg, params)[0], jax.random.PRNGKey(0),
        lambda key, shape, scale=0.02: jnp.zeros(shape))))
    assert made == set(m.specs(cfg)) and made <= set(
        _mixer_layer(kind, cfg, params)[1])
    # the step a tick holds: the rule's own answer under the row's key
    assert mixers.state_step_paths(cfg) == {kind: m.step_path(cfg)}
    assert mixers.state_step_paths(get_config("tiny")) == {}
    sv = engine.serving(**SERVE_KW)
    info = sv._exec.mesh_info()
    for row in MIXERS.values():
        assert info[row.step_key] == (m.step_path(cfg) if row is m else None)
    assert sv.health()[m.step_key] == m.step_path(cfg)
    assert sv._exec.state_passes == m.passes[m.step_path(cfg)]
    assert sv._exec._pool_keys == ("k", "v", *m.pool_keys)


# ------------------------------------------------------ the cache's leaves

def _ssm_leaves(cfg, cache):
    assert cache["ssm_state"].shape == (2, 3, 4, 8, 16)
    assert cache["ssm_state"].dtype == jnp.float32
    assert cache["ssm_conv"].shape == (2, 3, 3, 32 + 2 * 32)
    assert cache["k"].shape == (2, 7, 8, 2, 16)      # 16 wide: row-major
    assert T.init_paged_cache(cfg, 7, 8)["ssm_state"].shape[1] == 1
    assert T.PAGED_POOL_KEYS[-2:] == MIXERS["ssm"].pool_keys
    # 128-wide heads under 8 KV heads: the K/V leaves head-major, the same
    # numbers through them
    wide = ssm_file.tiny(head_dim=128, num_heads=2, num_kv_heads=1)
    assert T._head_major_leaves(wide)["k"]
    assert not T._head_major_leaves(cfg)["k"]
    assert T.init_paged_cache(wide, 7, 8)["k"].shape == (2, 7, 1, 8, 128)
    p = init_params(wide, jax.random.PRNGKey(0))
    toks = _tokens(21)
    cache = T.init_paged_cache(wide, 5, 8, dtype=jnp.float32)
    pad = jnp.zeros((1, 24), jnp.int32).at[:, :21].set(toks)
    got, cache = jax.jit(functools.partial(T.forward_paged, wide))(
        p, pad, cache, jnp.arange(1, 5, dtype=jnp.int32)[None],
        jnp.zeros((1,), jnp.int32), (jnp.arange(24) < 21)[None])
    assert cache["k"].shape == (2, 5, 1, 8, 128)
    assert float(jnp.abs(got[0, :21] - _forward(wide, p, toks)[0]).max()) < 1e-5


def _delta_leaves(cfg, cache):
    assert cache["delta_state"].shape == (6, 3, 2, 8, 128)
    assert cache["delta_state"].dtype == jnp.float32
    assert cache["delta_conv"].shape == (6, 3, 3 * (2 * 32 + 256))
    assert cache["k"].shape == (2, 7, 8, 4, 16)      # 16 wide: row-major
    assert T.cache_kind(cfg)[0] == "state" and T.cache_layers(cfg) == (2, 6)
    # 30 heads of 128 are no whole tiles of 8: the K/V leaves head-major
    assert T.pool_leaf_head_major(30, 128) and not T.pool_leaf_head_major(16, 128)
    wide = jax.eval_shape(lambda: T.init_paged_cache(
        get_config("olmo-hybrid-7b", num_layers=4), 5, 128))
    assert wide["k"].shape == (1, 5, 30, 128, 128)


def _conv_leaves(cfg, cache):
    # a slot's two rows side by side in ONE row
    assert cache["conv_tail"].shape == (8, 3, 2 * 64)
    assert cache["k"].shape == (2, 7, 8, 2, 16)
    assert T.cache_kind(cfg)[0] == "state" and T.cache_layers(cfg) == (2, 8)
    wide = jax.eval_shape(lambda: T.init_paged_cache(
        get_config("lfm2-8b-a1b", num_layers=14), 5, 128, slots=4))
    assert wide["k"].shape == (3, 5, 128, 8, 64)        # 6,144 B a token
    assert wide["conv_tail"].shape == (11, 4, 4096)     # 90,112 B a slot
    assert T.ssm_scan_chunks(cfg, 64, 21) is None


LEAVES = {"ssm": _ssm_leaves, "linear": _delta_leaves, "conv": _conv_leaves}


@pytest.mark.parametrize("kind", KINDS)
def test_the_cache_has_the_rows_leaves_with_no_page_axis(kind, models):
    cfg = models(kind)[0]
    cache = T.init_paged_cache(cfg, 7, 8, dtype=jnp.float32, slots=3)
    assert set(cache) == {"k", "v", *MIXERS[kind].pool_keys}
    LEAVES[kind](cfg, cache)


# ------------------------------------------- the rows a batch reads, writes

def _goes_on(run, toks, table, a, cache, keys, layers):
    """The next block continues the row: both halves = the whole, in
    ``layers`` (a model walked by kind: the mixer's layers before the first
    attention layer, for a block of more than one token attends within
    itself there and the engine starts no block behind rows a slot already
    holds)."""
    _, a2 = run(toks[:, 16:], a, table, jnp.full((1,), 16, jnp.int32),
                jnp.ones((1, 8), bool), state_slot=jnp.asarray([2]))
    _, whole = run(toks, cache, table, jnp.zeros((1,), jnp.int32),
                   jnp.ones((1, 24), bool))
    for key, atol in zip(keys, (1e-5, 1e-6)):
        np.testing.assert_allclose(a2[key][layers, 2], whole[key][layers, 0],
                                   atol=atol)


def _conv_goes_on(run, toks, table, a, cache, cfg, params):
    # a tick of three slots: slot 2 live behind its 16 rows, slot 0 live
    # behind a tail nobody reset (its start is not 0), slot 1 idle
    tables = jnp.zeros((3, 3), jnp.int32).at[2].set(table[0])
    tick_tok = jnp.zeros((3, 1), jnp.int32).at[2, 0].set(toks[0, 16])
    mask = jnp.asarray([[True], [False], [True]])
    logits, c = run(tick_tok, a, tables, jnp.asarray([4, 9, 16], jnp.int32),
                    mask)
    np.testing.assert_array_equal(c["conv_tail"][:, 1], a["conv_tail"][:, 1])
    assert float(jnp.abs(c["conv_tail"][:, 0] - a["conv_tail"][:, 0]).max()
                 ) > 0
    want = T.forward(cfg, params, toks[:, :17])[0, 16]
    np.testing.assert_allclose(logits[2, 0], want, atol=2e-4)
    # the first conv layer's tail of slot 2 is (z_15, z_16) of the sequence
    _, whole = run(toks[:, :17], cache, table, jnp.zeros((1,), jnp.int32),
                   jnp.ones((1, 17), bool))
    np.testing.assert_allclose(c["conv_tail"][0, 2], whole["conv_tail"][0, 0],
                               atol=1e-6)


@pytest.mark.parametrize("kind", KINDS)
def test_state_rows_follow_state_slot_and_start(kind, models):
    """Row b of the batch is state row b unless ``state_slot`` says
    otherwise; a start of 0 resets, any other continues (a conv model: two
    slots of different lengths advance side by side and an idle one keeps
    its tail)."""
    cfg, params, _ = models(kind)
    keys = MIXERS[kind].pool_keys
    toks = _tokens(24, seed=3)
    table = jnp.arange(1, 4, dtype=jnp.int32)[None]
    cache = T.init_paged_cache(cfg, 4, 8, dtype=jnp.float32, slots=3)
    dirty = dict(cache, **{k: cache[k] + 5.0 for k in keys})
    run = jax.jit(functools.partial(T.forward_paged, cfg, params))
    _, a = run(toks[:, :16], dirty, table, jnp.zeros((1,), jnp.int32),
               jnp.ones((1, 16), bool), state_slot=jnp.asarray([2]))
    _, b = run(toks[:, :16], cache, table, jnp.zeros((1,), jnp.int32),
               jnp.ones((1, 16), bool))
    np.testing.assert_allclose(a[keys[0]][:, 2], b[keys[0]][:, 0], atol=1e-6)
    np.testing.assert_array_equal(a[keys[0]][:, :2], dirty[keys[0]][:, :2])
    if kind == "conv":
        _conv_goes_on(run, toks, table, a, cache, cfg, params)
    else:
        _goes_on(run, toks, table, a, cache, keys,
                 slice(None) if kind == "ssm" else slice(3))


# ------------------------------------------------------------ a padded block

@pytest.mark.parametrize("kind", KINDS)
def test_a_padded_bucket_leaves_what_the_unpadded_prompt_does(kind, models):
    """What a mixer keeps after a block (a state and a tail, or the tail
    alone) is what it keeps after the block's real tokens; a row with no
    real token keeps what it had."""
    cfg, params, _ = models(kind)
    g, lp = _mixer_layer(kind, cfg, params)
    h = jnp.asarray(np.random.default_rng(2).standard_normal((1, 32, 64)),
                    jnp.float32)
    mixer = jax.jit(functools.partial(MIXERS[kind].mixer, g))
    out, kept = mixer(lp, h[:, :21])
    out_p, kept_p = mixer(lp, h, (jnp.arange(32) < 21)[None])
    np.testing.assert_allclose(out_p[:, :21], out, atol=1e-5)
    *state, tail = jax.tree_util.tree_leaves(kept)
    *state_p, tail_p = jax.tree_util.tree_leaves(kept_p)
    for s, s_p in zip(state, state_p):
        np.testing.assert_allclose(s_p, s, atol=1e-5)
    np.testing.assert_array_equal(tail_p, tail)
    # a row with no real token keeps what it had
    had = jax.tree_util.tree_map(lambda a: a + 1.0, kept)
    _, still = mixer(lp, h[:, :1], jnp.zeros((1, 1), bool), had)
    jax.tree_util.tree_map(np.testing.assert_array_equal, still, had)
    if kind == "conv":
        assert tail.shape == (1, 2, 64)
        # one token behind a tail: the three-term sum, the tail shifted
        out1, t1 = mixer(lp, h[:, 21:22], None, tail)
        np.testing.assert_allclose(
            out1[:, 0], mixer(lp, h[:, :22])[0][:, 21], atol=1e-6)
        np.testing.assert_array_equal(t1[:, 0], tail[:, 1])


# --------------------------------------- the mechanisms that know pages alone

REFUSALS = {
    "prefix sharing": ("prefix sharing", lambda e: e.serving(
        prefix_cache=True, **SERVE_KW)),
    "tiering": ("KV-page tiering", lambda e: e.serving(
        host_tier_pages=4, **SERVE_KW)),
    "extract and inject": ("KV-page tiering", lambda e: MeshExecutor(
        e.model, e.params, 13, 8, 3, prefix_cache=False, host_tier=True)),
    "speculative": ("speculative decoding", lambda e: e.serving(
        speculative=object(), **SERVE_KW)),
    "int8 pool": ("int8 pool", lambda e: e.serving(
        kv_dtype="int8", **SERVE_KW)),
    "int8 cache": ("int8 pool", lambda e: e.model.init_paged_cache(
        4, 8, kv_dtype="int8")),
    "copy-on-write": ("copy-on-write", lambda e: MeshExecutor(
        e.model, e.params, 13, 8, 3, prefix_cache=True)),
    "adapters": ("adapter", lambda e: T.forward_paged(
        e.model.config, e.params, jnp.zeros((1, 1), jnp.int32),
        e.model.init_paged_cache(4, 8), jnp.ones((1, 3), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.ones((1, 1), bool),
        adapters={"scale": jnp.ones((1,)), "factors": {}})),
    "adapter registry": ("multi-tenant adapters", lambda e: MeshExecutor(
        e.model, e.params, 13, 8, 3, prefix_cache=False, adapters=object())),
    "contiguous cache": ("contiguous cache", lambda e: e.generate(
        np.arange(4, dtype=np.int32)[None], max_new_tokens=2)),
    "training": ("training", lambda e: T.forward(
        e.model.config, e.params, jnp.zeros((1, 4), jnp.int32),
        deterministic=False)),
}


@pytest.mark.parametrize("what", list(REFUSALS))
@pytest.mark.parametrize("kind", KINDS)
def test_mechanisms_that_know_pages_alone_refuse_by_name(kind, what, models):
    """``cache_layout.REFUSED``'s rows for the kind ``state``, every one
    for every row of the table, by the row's own words."""
    named, call = REFUSALS[what]
    with pytest.raises(NotImplementedError, match=re.escape(
            MIXERS[kind].keeps.split(":")[0])) as e:
        call(models(kind)[2])
    assert named in str(e.value)


# ---------------------------------------- what the block is not built from

# what every kind refuses alike, by the shared list's words
SHARED = ((dict(norm="layernorm"), "RMSNorm"),
          (dict(parallel_residual=True), "parallel_residual"),
          (dict(post_layernorm=True), "post_layernorm"),
          (dict(kv_lora_rank=8, rotary_dim=8), "latent attention"),
          (dict(loop_passes=2), "loop_passes"),
          (dict(pipeline_stages=2), "pipeline_stages"),
          (dict(random_ltd=True), "random_ltd"))
SSM_ON = dict(ssm_heads=4, ssm_head_dim=8, ssm_state=16)
DELTA_ON = dict(linear_heads=4, linear_key_dim=8, linear_value_dim=16)
# each kind's own: ``(NotImplementedError cases, ValueError cases)``
OWN = {
    "ssm": (((dict(num_experts=(4, 4)), "per-layer expert counts"),),
            ((dict(ssm_groups=3), "whole groups"),)),
    "linear": (((dict(num_experts=4), "expert layers"),
                (dict(attn_bias=True), "attn_bias"),
                (SSM_ON, "state-space layers"),
                (dict(layer_pattern=("linear", "window") * 4),
                 "window layers in one layer_pattern"),
                (dict(layer_pattern=("linear",) * 8), "not of both kinds")),
               ((dict(linear_key_dim=0), "linear_key_dim"),)),
    "conv": (((SSM_ON, "state-space layers"), (DELTA_ON, "delta layers"),
              (dict(layer_pattern=("conv", "window") * 5),
               "window layers in one layer_pattern"),
              (dict(layer_pattern=("conv",) * 10), "not of both kinds"),
              (dict(attn_bias=True), "attn_bias"),
              (dict(num_experts=(1,) * 10), "per-layer expert counts"),
              (dict(norm_after=True), "sandwich_norm or norm_after")),
             ((dict(conv_taps=1), "conv_taps > 1"),)),
}


@pytest.mark.parametrize("kind", KINDS)
def test_what_the_block_is_not_built_from_is_refused(kind):
    key = jax.random.PRNGKey(0)
    tiny, m = TINY[kind][0], MIXERS[kind]
    refused, malformed = OWN[kind]
    for over, match in SHARED + refused:
        with pytest.raises(NotImplementedError, match=match) as e:
            mixers.check(tiny(**over))
        assert m.words in str(e.value)      # both names in the message
    # where the model is built the check has run: init_params refuses too
    for over, match in SHARED[:1] + (refused[:1] if kind != "ssm" else ()):
        with pytest.raises(NotImplementedError, match=match):
            init_params(tiny(**over), key)
    for over, match in malformed:
        with pytest.raises(ValueError, match=match):
            init_params(tiny(**over), key)
    assert mixers.check(tiny()) is None
    if kind == "ssm":
        # expert layers behind a mixer are built since PR 47; a count a
        # layer (the pyramid) and leading dense layers are still not
        with pytest.raises(NotImplementedError, match="dense_layers"):
            init_params(tiny(num_experts=4, dense_layers=1,
                             moe_drop_tokens=False), key)
        assert "router" in init_params(tiny(num_experts=4), key)["layers"]
        return
    # a pattern names the kind and the model has no such mixer; an unknown kind
    with pytest.raises(ValueError, match=re.escape(f"no {m.words}")):
        T.layer_plan(tiny(**{m.field: 0}))
    with pytest.raises(ValueError, match=re.escape(
            "full | window | " + " | ".join(MIXERS))):
        T.layer_plan(tiny(layer_pattern=(kind, "mamba") * 5))
    if kind == "linear":
        with pytest.raises(NotImplementedError, match="sandwich_norm"):
            T._check_loop(tiny(sandwich_norm=True))
        # QK-norm under a layer_pattern with state-space layers (R5 (f)):
        # the attention layers' alone, as here
        granite = get_config(
            "granite-4.0-h-small", num_layers=6, hidden_size=64,
            intermediate_size=24, num_heads=4, num_kv_heads=2, head_dim=16,
            vocab_size=256, ssm_heads=4, ssm_head_dim=32, ssm_state=16,
            ssm_chunk=8, num_experts=4, moe_top_k=2, qk_norm=True,
            dtype=jnp.float32)
        leaves = jax.eval_shape(lambda: init_params(granite, key))["layers"]
        assert "q_norm_scale" in leaves["full_moe"]
        assert "q_norm_scale" not in leaves["ssm_moe"]
        assert granite.param_count == sum(
            int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
                jax.eval_shape(lambda: init_params(granite, key))))
