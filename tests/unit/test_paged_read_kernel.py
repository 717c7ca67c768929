"""A decode tick's paged read through ``ops/pallas/paged_read.py`` (each live
page fetched once from where it lies) against ``_attention_paged``'s
gather-then-attend loop under the same plan, in interpret mode asked for by
name, and the rule that chooses between them (``kv_read_path``): the kernel
for one token a slot over bfloat16 K and V leaves of whole-lane heads stored
row-major or head-major, where a program may hold a kernel at all; the gather
elsewhere.  A latent leaf's read (``latent_read`` against
``_attention_latent_paged``'s loop) by the same cases."""
import functools
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.mixers import common as MX
from deepspeed_tpu.ops.pallas import paged_read as PR

HEAD_MAJOR = (0, 1, 3, 2, 4)
LATENT = T.LATENT_PAGE_ROWS_MINOR       # as the v5e stores a latent leaf
SLOTS, MAXP = 5, 4
TABLE = jnp.arange(1, 1 + SLOTS * MAXP, dtype=jnp.int32).reshape(SLOTS, MAXP)
PAGES = 1 + SLOTS * MAXP            # a layer's, the trash page counted in


@pytest.fixture
def every_block(monkeypatch):
    """The tests' pages are a few KB: the bound on a block's bytes is the
    chip's business (tools/paged_read_bench.py), not the kernel's."""
    monkeypatch.setattr(PR, "MIN_BLOCK_BYTES", 0)
    # the kernel is traced once a shape: a test that answers for
    # ``pairs_a_step`` must not be handed another test's trace
    PR.paged_read.clear_cache()
    PR.latent_read.clear_cache()


def _queries(slots=8, heads=16, dtype=jnp.bfloat16):
    """A tick's queries as the rule sees them: ``[B, Hq]`` and a dtype."""
    return jax.ShapeDtypeStruct((slots, heads), dtype)


def _cfg(hq, hkv, **over):
    return get_config("olmoe-1b-7b", **{**dict(
        num_layers=1, num_heads=hq, num_kv_heads=hkv, head_dim=128,
        hidden_size=64, dtype=jnp.bfloat16), **over})


def _both_ways(cfg, q, pools, read, order, monkeypatch):
    def attend():
        # a fresh function a path: jax caches a trace by function
        return np.asarray(jax.jit(lambda q, p: T._attention_paged(
            cfg, q, p, read, order))(q, pools), np.float32)

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: None)
    want = attend()
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    assert T.kv_read_path(pools, order, _queries(*q.shape[::2])) == "pages"
    return attend(), want


# what each slot is: one row of its first page; the middle of its second
# page (a last page partly filled); the last row of a page; masked, no real
# token; every page of its table row full
START = jnp.array([0, 20, 47, 5, 63], jnp.int32)
MASK = jnp.array([[True], [True], [True], [False], [True]])

LEAVES = {
    "head-major-30x128": (HEAD_MAJOR, 30, 30, 16),
    "row-major-16x128": (None, 16, 16, 8),
    "row-major-16x128-groups-of-4": (None, 64, 16, 8),
    "head-major-4x128-groups-of-2": (HEAD_MAJOR, 8, 4, 16),
}


@pytest.mark.parametrize("pairs", [1, 4], ids=["a-pair-a-step", "four"])
@pytest.mark.parametrize("leaf", list(LEAVES))
def test_kernel_reads_what_the_gather_reads(leaf, pairs, every_block,
                                            monkeypatch):
    """Five slots, one with no real token (its output 0, not NaN), a list of
    ten live pairs of twenty with the dead ones past the total, one step of
    the gather's loop (10 pairs a step) for the kernel's ten or three."""
    order, hq, hkv, page = LEAVES[leaf]
    monkeypatch.setattr(PR, "pairs_a_step", lambda block: pairs)
    ks = jax.random.split(jax.random.PRNGKey(hq), 3)
    start = START * page // 16
    read = T._paged_read_plan(TABLE, start, MASK, page)
    steps, slot = int(read[0]), np.asarray(read[1]).reshape(-1)
    live = int((slot < SLOTS).sum())
    assert steps == 1 and live == 10 and set(slot[live:]) == {SLOTS}
    pools = {n: jax.random.normal(k, (PAGES, page, hkv, 128), jnp.bfloat16)
             for n, k in zip("kv", ks)}
    q = jax.random.normal(ks[2], (SLOTS, 1, hq, 128), jnp.bfloat16)
    got, want = _both_ways(_cfg(hq, hkv), q, pools, read, order, monkeypatch)
    # the repo's bfloat16 tolerance: an ulp of the output's size
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert np.all(got[3] == 0) and np.all(want[3] == 0)
    # slot 0 holds one row: its output is that row of V
    np.testing.assert_allclose(
        got[0, 0], np.repeat(np.asarray(pools["v"][1, 0], np.float32),
                             hq // hkv, axis=0), atol=1e-6)


@pytest.mark.parametrize("slots", [2, 3], ids=["steps-of-4", "steps-of-6"])
def test_a_list_of_several_steps(slots, every_block, monkeypatch):
    """Long slots: the gather's loop runs several steps of ``2 x slots``
    pairs, the kernel's grid as many steps as there are live pairs."""
    page, maxp = 16, 6
    table = jnp.arange(1, 1 + slots * maxp, dtype=jnp.int32).reshape(
        slots, maxp)[::-1]
    start = jnp.array([95, 40, 70][:slots], jnp.int32)
    read = T._paged_read_plan(table, start, jnp.ones((slots, 1), bool), page)
    assert int(read[0]) == 3
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    pools = {n: jax.random.normal(k, (1 + slots * maxp, page, 4, 128),
                                  jnp.bfloat16) for n, k in zip("kv", ks)}
    q = jax.random.normal(ks[2], (slots, 1, 4, 128), jnp.bfloat16)
    got, want = _both_ways(_cfg(4, 4), q, pools, read, HEAD_MAJOR,
                           monkeypatch)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_no_live_pair_at_all(every_block, monkeypatch):
    """Every slot idle: one grid step that leaves every slot at its start."""
    read = T._paged_read_plan(TABLE, START, jnp.zeros((SLOTS, 1), bool), 16)
    assert int(read[0]) == 0
    pools = {n: jnp.ones((PAGES, 16, 4, 128), jnp.bfloat16) for n in "kv"}
    got, want = _both_ways(_cfg(4, 4), jnp.ones((SLOTS, 1, 4, 128),
                                                jnp.bfloat16),
                           pools, read, HEAD_MAJOR, monkeypatch)
    assert np.all(got == 0) and np.all(want == 0)


def _kanana(**over):
    """Tiny widths with a latent row the tile plan takes: 128 value columns
    and a 16-wide rotated key row, two layers (one dense, one of experts)."""
    return get_config("kanana-2-30b-a3b", **{**dict(
        num_layers=2, hidden_size=64, intermediate_size=96, vocab_size=256,
        max_seq_len=1024, num_heads=4, head_dim=24, v_head_dim=16,
        rotary_dim=16, kv_lora_rank=128, num_experts=16, moe_experts_held=4,
        moe_top_k=3, moe_intermediate_size=32, dtype=jnp.bfloat16), **over})


def _latent_both_ways(table, start, mask, monkeypatch, page=128, seed=0):
    """The absorbed read of a toy Kanana layer over a random latent leaf by
    the gather (the order not observed) and by the kernel (observed stored
    page-rows-minor), under one plan: ``(got, want, steps)``."""
    cfg = _kanana()
    slots = table.shape[0]
    read = T._paged_read_plan(table, start, mask, page)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = jax.random.normal(ks[0], (int(table.max()) + 1, page, 144),
                             jnp.bfloat16)
    q = jax.random.normal(ks[1], (slots, 1, 4, 24), jnp.bfloat16)
    wkv_b = jax.random.normal(ks[2], (128, 4 * (8 + 16)), jnp.bfloat16) * .1
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)

    def attend(order):
        # a fresh function a path: jax caches a trace by function
        text = str(jax.make_jaxpr(lambda q, p: T._attention_latent_paged(
            cfg, q, wkv_b, p, read, order))(q, pool))
        assert ("name=latent_read" in text) == (order is not None)
        return np.asarray(jax.jit(lambda q, p: T._attention_latent_paged(
            cfg, q, wkv_b, p, read, order))(q, pool), np.float32)

    return attend(LATENT), attend(None), int(read[0])


@pytest.mark.parametrize("pairs", [1, 3], ids=["a-pair-a-step", "three"])
def test_latent_kernel_reads_what_the_gather_reads(pairs, every_block,
                                                   monkeypatch):
    """The five slots of the K/V test over a latent leaf: one row, a limit
    in mid-page, a page's last row, no real token (its output 0, not NaN),
    a full table row; ten live pairs, one step of the gather's loop for the
    kernel's ten or four (whose last step runs two pairs past the total,
    masked whole)."""
    monkeypatch.setattr(PR, "pairs_a_step", lambda block, step: pairs)
    got, want, steps = _latent_both_ways(
        TABLE, jnp.array([0, 160, 383, 40, 511], jnp.int32), MASK,
        monkeypatch)
    assert steps == 1
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert np.all(got[3] == 0) and np.all(want[3] == 0)
    assert np.abs(want[[0, 1, 2, 4]]).min(-1).max() > 0


def test_latent_pairs_past_the_list_fold_nothing(every_block, monkeypatch):
    """Every table row full: twenty live pairs, the whole list; the kernel's
    seventh step of three reads one place past it, which the wrapper's
    padding fills with slot 0 and a limit of 0: masked by the total, not by
    what it holds."""
    monkeypatch.setattr(PR, "pairs_a_step", lambda block, step: 3)
    got, want, steps = _latent_both_ways(
        TABLE, jnp.full((SLOTS,), 511, jnp.int32), jnp.ones((SLOTS, 1), bool),
        monkeypatch, seed=3)
    assert steps == 2
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_latent_list_of_several_steps(every_block, monkeypatch):
    """Long slots: the gather's loop runs three steps of four pairs, the
    kernel's grid four steps of three."""
    monkeypatch.setattr(PR, "pairs_a_step", lambda block, step: 3)
    table = jnp.arange(1, 13, dtype=jnp.int32).reshape(2, 6)[::-1]
    got, want, steps = _latent_both_ways(
        table, jnp.array([760, 320], jnp.int32), jnp.ones((2, 1), bool),
        monkeypatch, seed=7)
    assert steps == 3
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_latent_no_live_pair_at_all(every_block, monkeypatch):
    got, want, steps = _latent_both_ways(
        TABLE, START, jnp.zeros((SLOTS, 1), bool), monkeypatch)
    assert steps == 0 and np.all(got == 0) and np.all(want == 0)


def _pools(heads=16, width=128, dtype=jnp.bfloat16, page=16, scales=False):
    leaf = jax.ShapeDtypeStruct((40, page, heads, width), dtype)
    pools = {"k": leaf, "v": leaf}
    if scales:
        plane = jax.ShapeDtypeStruct((40, page), jnp.float32)
        pools.update(k_scale=plane, v_scale=plane)
    return pools


def _latent(width=576, page=128, dtype=jnp.bfloat16):
    return {"latent": jax.ShapeDtypeStruct((40, page, width), dtype)}


RULE = {
    "a-decode-tick-where-a-kernel-may-run": (
        dict(pools=_pools(), pool_order=None), True, "pages"),
    "a-decode-tick-compiled-for-the-chip": (
        dict(pools=_pools(30), pool_order=HEAD_MAJOR), False, "pages"),
    "an-order-a-leaf": (
        dict(pools=_pools(4), pool_order={"k": HEAD_MAJOR, "v": HEAD_MAJOR}),
        True, "pages"),
    "a-prompts-or-a-verify-block": (
        dict(pools=_pools(), pool_order=None, tokens=5), True, "gather"),
    "a-leaf-stored-page-rows-minor": (
        dict(pools=_pools(32, 64), pool_order=(0, 1, 3, 4, 2)), True,
        "gather"),
    "k-and-v-stored-in-two-orders": (
        dict(pools=_pools(), pool_order={"k": (0, 1, 3, 4, 2), "v": None}),
        True, "gather"),
    "a-quantised-pools-scale-planes": (
        dict(pools=_pools(dtype=jnp.int8, scales=True), pool_order=None),
        True, "gather"),
    "float32-leaves": (
        dict(pools=_pools(dtype=jnp.float32), pool_order=None), True,
        "gather"),
    "alibi-a-window-or-a-sink": (
        dict(pools=_pools(), pool_order=None, plain=False), True, "gather"),
    "float32-queries-over-a-bfloat16-pool": (
        dict(pools=_pools(), pool_order=None,
             query=_queries(dtype=jnp.float32)), True, "gather"),
    "more-slots-than-stay-on-chip": (
        dict(pools=_pools(), pool_order=None, query=_queries(2048, 64)),
        True, "gather"),
    "a-page-block-too-large-for-two-buffers-each": (
        dict(pools=_pools(128, page=256), pool_order=None), True, "gather"),
    "a-192-wide-head": (
        dict(pools=_pools(4, 192), pool_order=HEAD_MAJOR), True, "gather"),
    "eight-heads-row-major-half-a-tile": (
        dict(pools=_pools(8), pool_order=None), True, "gather"),
    "a-backend-that-is-not-a-tpu": (
        dict(pools=_pools(), pool_order=None), None, "gather"),
    # a latent leaf [N, page, r + rd], r of its columns a row's values
    "a-latent-leaf-stored-page-rows-minor": (
        dict(pools=_latent(), pool_order=LATENT, values=512), True, "pages"),
    "a-latent-tick-compiled-for-the-chip": (
        dict(pools=_latent(), pool_order={"latent": LATENT}, values=512),
        False, "pages"),
    "a-latent-prompts-or-a-verify-block": (
        dict(pools=_latent(), pool_order=LATENT, values=512, tokens=5), True,
        "gather"),
    "a-latent-leaf-observed-row-major": (
        dict(pools=_latent(), pool_order=None, values=512), True, "gather"),
    "a-float32-latent-leaf": (
        dict(pools=_latent(dtype=jnp.float32), pool_order=LATENT, values=512,
             query=_queries(dtype=jnp.float32)), True, "gather"),
    "float32-queries-over-a-bfloat16-latent-leaf": (
        dict(pools=_latent(), pool_order=LATENT, values=512,
             query=_queries(dtype=jnp.float32)), True, "gather"),
    "latent-values-that-are-no-whole-lanes": (
        dict(pools=_latent(), pool_order=LATENT, values=120), True, "gather"),
    "a-latent-page-of-half-a-lane-tile": (
        dict(pools=_latent(page=64), pool_order=LATENT, values=512), True,
        "gather"),
    "more-latent-slots-than-stay-on-chip": (
        dict(pools=_latent(), pool_order=LATENT, values=512,
             query=_queries(512, 128)), True, "gather"),
    "a-latent-leaf-where-no-kernel-may-run": (
        dict(pools=_latent(), pool_order=LATENT, values=512), None, "gather"),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_reads_what_the_trace_can_observe(case, every_block,
                                                   monkeypatch):
    kw, interpret, want = RULE[case]
    if interpret is not None:
        monkeypatch.setattr(MX, "_pallas_interpret", lambda: interpret)
    assert T.kv_read_path(**{"query": _queries(), **kw}) == want


def test_a_block_too_small_to_pay_for_its_step_keeps_the_gather(monkeypatch):
    """The observable that tells a page of many heads from one of few: the
    bytes of one ``[Hkv, page, hd]`` block."""
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    sizes = {heads: T.kv_read_path(_pools(heads, page=128), HEAD_MAJOR,
                                   _queries(heads=heads))
             for heads in (2, 4, 30)}
    assert sizes == {2: "gather", 4: "pages", 30: "pages"}
    assert PR.pairs_a_step(4 * 128 * 128 * 2) == 4
    assert PR.pairs_a_step(30 * 128 * 128 * 2) == 1
    with pytest.raises(NotImplementedError, match="no tile plan"):
        PR.paged_read(jnp.zeros((2, 8, 64), jnp.bfloat16),
                      *(jnp.zeros((9, 16, 8, 64), jnp.bfloat16),) * 2,
                      0, *(jnp.zeros((4,), jnp.int32),) * 3, axes="ktd",
                      scale=1.0, interpret=True)


def test_a_sharded_mesh_keeps_the_gather(monkeypatch):
    """``pallas_call`` has no partitioning rule: the rule asks the mesh."""
    from deepspeed_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", None)
    assert T.kv_read_path(_pools(page=128), None, _queries()) == "pages"
    assert T.kv_read_path(_latent(), LATENT, _queries(),
                          values=512) == "pages"
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH",
                        mesh_mod.build_mesh(mesh_mod.MeshLayout(dp=2),
                                            jax.devices()[:2]))
    assert T.kv_read_path(_pools(page=128), None, _queries()) == "gather"
    assert T.kv_read_path(_latent(), LATENT, _queries(),
                          values=512) == "gather"


@functools.lru_cache(maxsize=None)
def _params(config):
    """``config()``'s parameters in its dtype, made once a module: two tests
    a model read them, and making them is seconds."""
    cfg = config()
    return jax.tree_util.tree_map(lambda a: a.astype(cfg.dtype),
                                  init_params(cfg, jax.random.PRNGKey(1)))


def _olmo(**over):
    """Tiny widths, one whole period (3 delta layers + 1 of attention), four
    KV heads of 128 (kept head-major, as the published 30 are)."""
    return get_config("olmo-hybrid-7b", **{**dict(
        num_layers=4, hidden_size=64, intermediate_size=96, num_heads=4,
        num_kv_heads=4, head_dim=128, vocab_size=256, linear_heads=4,
        linear_key_dim=8, linear_value_dim=64, linear_chunk=8,
        max_seq_len=512, dtype=jnp.bfloat16), **over})


CACHES = {
    # model, overrides, init_paged_cache's kw, the order observed -> paths
    "head-major-leaves-beside-a-state": (
        _olmo(), dict(slots=2), None, {"k": "pages", "v": "pages"}),
    "row-major-k-and-v": (
        get_config("ouro-2.6b", num_layers=2, hidden_size=64,
                   intermediate_size=96, num_heads=16, num_kv_heads=16,
                   head_dim=128, vocab_size=256, max_seq_len=512,
                   dtype=jnp.bfloat16), {}, None,
        {"k": "pages", "v": "pages"}),
    "the-scale-planes-and-int8-rows": (
        get_config("ouro-2.6b", num_layers=2, hidden_size=64,
                   intermediate_size=96, num_heads=16, num_kv_heads=16,
                   head_dim=128, vocab_size=256, max_seq_len=512,
                   dtype=jnp.bfloat16), dict(kv_dtype="int8"), None,
        {"k": "gather", "v": "gather", "k_scale": "gather",
         "v_scale": "gather"}),
    "the-latent-leaf": (
        _kanana(), dict(page=128), LATENT, {"latent": "pages"}),
    "the-latent-leaf-observed-row-major": (
        _kanana(), dict(page=128), None, {"latent": "gather"}),
    "a-latent-leaf-of-values-that-are-no-whole-lanes": (
        _kanana(kv_lora_rank=120, rotary_dim=8), dict(page=128), LATENT,
        {"latent": "gather"}),
    "two-kinds-of-layer": (
        get_config("mimo-v2.5", num_layers=7, hidden_size=64,
                   intermediate_size=96, vocab_size=256, max_seq_len=512,
                   num_heads=8, num_kv_heads=2, window_kv_heads=8,
                   head_dim=192, v_head_dim=128, rotary_dim=8,
                   window_size=16, num_experts=16, moe_experts_held=4,
                   moe_top_k=3, moe_intermediate_size=32,
                   dtype=jnp.bfloat16), {},
        {"k": (0, 1, 3, 4, 2), "v": None, "k_window": (0, 1, 3, 4, 2),
         "v_window": None},
        # 192-wide keys; the window layers' read carries a window and a sink
        {"k": "gather", "v": "gather", "k_window": "gather",
         "v_window": "gather"}),
}


@pytest.mark.parametrize("case", list(CACHES))
def test_every_leaf_of_a_cache_says_its_path(case, every_block, monkeypatch):
    cfg, cache_kw, order, want = CACHES[case]
    cache_kw = dict(cache_kw)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    cache = jax.eval_shape(lambda: T.init_paged_cache(
        cfg, 9, cache_kw.pop("page", 16), dtype=jnp.bfloat16, **cache_kw))
    assert T.kv_read_paths(cfg, cache, order) == want
    # what the executor reports is what the trace holds: one kernel a layer
    # with attention where K and V are said to be read by pages (in a layer
    # scan's body once), none for any other
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, cfg.dtype),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    traced = str(jax.make_jaxpr(lambda p, c: T.forward_paged(
        cfg, p, jnp.zeros((2, 1), jnp.int32), c,
        jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4),
        jnp.array([3, 9], jnp.int32), jnp.ones((2, 1), bool),
        pool_order=order))(params, cache))
    # (a latent model's dense layer and its expert layers: a scan each)
    walked = (T.kind_layers(cfg)["full"][1] if T.is_hybrid(cfg)
              else len(T.layer_groups(cfg)) if T.is_grouped(cfg) else 1)
    # (the kernel is jitted: one call a site, its one body shared)
    assert len(re.findall(r"jit\[\s*name=(?:paged|latent)_read", traced)) == (
        walked if "pages" in want.values() else 0)
    # nowhere but on a TPU: every leaf gathered
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: None)
    assert set(T.kv_read_paths(cfg, cache, order).values()) == {"gather"}


# model: its config, the page, the prompts' block, a prompt a slot, the order
# handed to ``forward_paged``, the cache's kw, the layers' call sites of the
# kernel (Olmo's one attention layer; Kanana's dense layer's scan and its
# expert layer's), the paged leaves and the kernel's name
TICKS = {
    "olmo-hybrid": (_olmo, 16, 20, (5, 9, 3, 20), None, dict(slots=4), 1,
                    ("k", "v"), "paged_read"),
    "kanana": (_kanana, 128, 256, (40, 130, 3, 200), LATENT, {}, 2,
               ("latent",), "latent_read"),
}


@pytest.mark.parametrize("model", list(TICKS))
def test_a_decode_tick_through_forward_paged_is_the_gather_ticks(
        model, every_block, monkeypatch):
    """A prompt a slot (its block attends within itself either way) and two
    ticks of ``forward_paged`` of a toy model over four slots, one masked
    and one in its second page, with the kernel in the attention layers: the
    gather tick's logits to bfloat16 rounding, and the same pool."""
    config, page, block, lengths, order, cache_kw, sites, leaves, name = \
        TICKS[model]
    cfg, params = config(), _params(config)
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(4, 2)
    mask = jnp.array([[True], [True], [False], [True]])
    toks = jax.random.randint(jax.random.PRNGKey(3), (4, block), 0, 256)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)

    def stepper():
        # a fresh function a path: jax caches a trace by function
        return jax.jit(lambda c, t, s, m, at: T.forward_paged(
            cfg, params, t, c, table[at], s, m, pool_order=order,
            **({"state_slot": at} if t.shape[1] > 1 and T.has_state(cfg)
               else {})))

    filled = T.init_paged_cache(cfg, 9, page, dtype=jnp.bfloat16, **cache_kw)
    prompt = stepper()
    for b, n in enumerate(lengths):                 # a prompt a slot
        _, filled = prompt(filled, toks[b:b + 1], jnp.zeros((1,), jnp.int32),
                           (jnp.arange(block) < n)[None],
                           jnp.array([b], jnp.int32))

    def ticks():
        cache, step, outs = filled, stepper(), []
        for add in (0, 1):
            logits, cache = step(
                cache, toks[:, :1] + add, jnp.array(lengths, jnp.int32) + add,
                mask, jnp.arange(4, dtype=jnp.int32))
            outs.append(np.asarray(logits, np.float32))
        traced = str(jax.make_jaxpr(lambda c: T.forward_paged(
            cfg, params, toks[:, :1], c, table, jnp.zeros((4,), jnp.int32),
            mask, pool_order=order))(cache))
        return outs, cache, traced

    monkeypatch.setattr(PR, "MIN_BLOCK_BYTES", 1 << 30)
    want, cache_g, text_g = ticks()
    assert f"name={name}" not in text_g
    monkeypatch.setattr(PR, "MIN_BLOCK_BYTES", 0)
    got, cache_k, text_k = ticks()
    assert len(re.findall(rf"jit\[\s*name={name}", text_k)) == sites
    for a, b in zip(got, want):
        np.testing.assert_allclose(a[[0, 1, 3]], b[[0, 1, 3]], atol=3e-2,
                                   rtol=3e-2)
        assert np.array_equal(a[[0, 1, 3]].argmax(-1), b[[0, 1, 3]].argmax(-1))
    # the first layer's rows do not depend on the read
    for leaf in leaves:
        np.testing.assert_allclose(
            np.asarray(cache_k[leaf][0], np.float32),
            np.asarray(cache_g[leaf][0], np.float32), atol=0)


# model: its config, the page, a slot's rows, its paged leaves, and the order
# the test observes in the compiler's place where the kernel is to read (the
# CPU stores every leaf row-major; a latent leaf asks to be seen as the v5e
# stores it)
ENGINES = {
    "olmo-hybrid": (_olmo, 16, 64, ("k", "v"), None),
    "kanana": (_kanana, 128, 256, ("latent",), LATENT),
}


@pytest.mark.parametrize("model", list(ENGINES))
def test_the_engine_says_how_it_reads(model, every_block, monkeypatch):
    """Through ``engine.serving``: the executor's report, ``health()``, the
    ready line, ``gathered_rows`` on every ``serve.decode`` span (live pages
    where the kernel reads, whole steps where the gather does), and the
    gather engine's tokens."""
    from deepspeed_tpu.inference import execution
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh
    from deepspeed_tpu.utils.logging import logger

    config, page, rows, leaves, seen = ENGINES[model]
    engine = deepspeed_tpu.init_inference(
        model=CausalLM(config()), params=_params(config), dtype="bf16",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=f"r{i}", arrival_time=0.0, max_new_tokens=5,
                    input_ids=rng.integers(0, 256, (5 + 9 * i,)
                                           ).astype(np.int32))
            for i in range(4)]

    class Lines(logging.Handler):
        def __init__(self):
            super().__init__()
            self.lines = []

        def emit(self, record):
            self.lines.append(record.getMessage())

    def run():
        said = Lines()
        logger.addHandler(said)
        try:
            sv = engine.serving(b_slots=3, page_size=page, max_model_len=rows)
        finally:
            logger.removeHandler(said)
        ready = [ln for ln in said.lines if "serving engine ready" in ln]
        get_tracer().reset()    # another test's spans are not this run's
        configure_tracer(enabled=True)
        try:
            out = {r.rid: list(r.output_ids) for r in sv.run(reqs)}
            attrs = [s.attrs for s in get_tracer().recorder.snapshot()
                     if isinstance(s, Span) and s.name == "serve.decode"
                     and s.attrs and "kv_bytes" in s.attrs]
        finally:
            configure_tracer(enabled=False)
            get_tracer().reset()
        assert attrs and sv.page_accounting()["balanced"]
        return sv, out, attrs, ready[-1]

    def says(sv, ready, path):
        report = {leaf: path for leaf in leaves}
        assert sv._exec.mesh_info()["kv_read"] == report
        assert sv.health()["kv_read"] == report
        assert "kv_read=" + ",".join(f"{leaf}:{path}"
                                     for leaf in leaves) in ready

    sv, want, attrs, ready = run()
    says(sv, ready, "gather")
    # 3 slots x 2 pairs a step x a page's rows: whole steps
    step = 3 * 2 * page
    assert {a["gathered_rows"] % step for a in attrs} == {0}
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    if seen is not None:
        monkeypatch.setattr(execution, "paged_pool_order", lambda leaf: seen)
    sv, got, attrs, ready = run()
    says(sv, ready, "pages")
    assert any(a["gathered_rows"] % step for a in attrs)
    assert all(a["gathered_rows"] % page == 0
               and a["live_rows"] <= a["gathered_rows"] < a["live_rows"]
               + page * 3 for a in attrs)
    assert got == want


def test_gathered_rows_follows_the_path():
    """The host's copy of the read's size: whole steps of ``2 x slots``
    pairs for the gather, the live pages for the kernel."""
    from deepspeed_tpu.inference.cache_layout import CacheLayout

    lengths = [130, 5, 700]
    assert T.paged_read_rows(lengths, 128, 16, 4) == 16 * 128
    assert T.paged_read_rows(lengths, 128, 16, 4, whole_steps=False) \
        == 9 * 128
    lay = CacheLayout(_olmo(), b_slots=4, page_size=128, pages_per_slot=16,
                      num_pages=65)
    assert lay.decode_attrs(lengths, 4)["gathered_rows"] == 16 * 128
    lay.kv_read_pages = True
    assert lay.decode_attrs(lengths, 4)["gathered_rows"] == 9 * 128
