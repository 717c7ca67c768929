"""A decode tick's paged read through ``ops/pallas/paged_read.py`` (each live
page fetched once from where it lies) against ``_attention_paged``'s
gather-then-attend loop under the same plan, in interpret mode asked for by
name, and the rule that chooses between them (``kv_read_path``): the kernel
for one token a slot over bfloat16 K and V leaves of whole-lane heads stored
row-major or head-major, where a program may hold a kernel at all; the gather
elsewhere."""
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


def _pools(heads=16, width=128, dtype=jnp.bfloat16, page=16, scales=False):
    leaf = jax.ShapeDtypeStruct((40, page, heads, width), dtype)
    pools = {"k": leaf, "v": leaf}
    if scales:
        plane = jax.ShapeDtypeStruct((40, page), jnp.float32)
        pools.update(k_scale=plane, v_scale=plane)
    return pools


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
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH",
                        mesh_mod.build_mesh(mesh_mod.MeshLayout(dp=2),
                                            jax.devices()[:2]))
    assert T.kv_read_path(_pools(page=128), None, _queries()) == "gather"


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
        get_config("kanana-2-30b-a3b", num_layers=2, hidden_size=64,
                   intermediate_size=96, vocab_size=256, max_seq_len=512,
                   num_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
                   kv_lora_rank=120, num_experts=16, moe_experts_held=4,
                   moe_top_k=3, moe_intermediate_size=32,
                   dtype=jnp.bfloat16), {}, None, {"latent": "gather"}),
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
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    cache = jax.eval_shape(lambda: T.init_paged_cache(
        cfg, 9, 16, dtype=jnp.bfloat16, **cache_kw))
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
    walked = T.kind_layers(cfg)["full"][1] if T.is_hybrid(cfg) else 1
    # (the kernel is jitted: one call a site, its one body shared)
    assert len(re.findall(r"jit\[\s*name=paged_read", traced)) == (
        walked if want.get("k") == "pages" else 0)
    # nowhere but on a TPU: every leaf gathered
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: None)
    assert set(T.kv_read_paths(cfg, cache, order).values()) == {"gather"}


def test_a_decode_tick_through_forward_paged_is_the_gather_ticks(
        every_block, monkeypatch):
    """A prompt's block (it attends within itself either way) and two ticks
    of ``forward_paged`` of a toy Olmo-Hybrid over four slots, one masked and
    one in its second page, with the kernel in the attention layers: the
    gather tick's logits to bfloat16 rounding, and the same pool."""
    cfg = _olmo()
    params = jax.tree_util.tree_map(
        lambda a: a.astype(cfg.dtype),
        init_params(cfg, jax.random.PRNGKey(2)))
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(4, 2)
    mask = jnp.array([[True], [True], [False], [True]])
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)

    def ticks():
        cache = T.init_paged_cache(cfg, 9, 16, dtype=jnp.bfloat16, slots=4)
        step = jax.jit(lambda c, t, s, m, at: T.forward_paged(
            cfg, params, t, c, table[at], s, m,
            **({"state_slot": at} if t.shape[1] > 1 else {})))
        toks = jax.random.randint(jax.random.PRNGKey(3), (4, 20), 0, 256)
        outs = []
        for b, n in enumerate((5, 9, 3, 20)):       # a prompt a slot
            logits, cache = step(
                cache, toks[b:b + 1], jnp.zeros((1,), jnp.int32),
                (jnp.arange(20) < n)[None], jnp.array([b], jnp.int32))
        for add in (0, 1):
            start = jnp.array([5, 9, 3, 20], jnp.int32) + add
            logits, cache = step(cache, toks[:, :1] + add, start, mask,
                                 jnp.arange(4, dtype=jnp.int32))
            outs.append(np.asarray(logits, np.float32))
        traced = str(jax.make_jaxpr(lambda c: T.forward_paged(
            cfg, params, toks[:, :1], c, table,
            jnp.zeros((4,), jnp.int32), mask))(cache))
        return outs, cache, traced

    monkeypatch.setattr(PR, "MIN_BLOCK_BYTES", 1 << 30)
    want, cache_g, text_g = ticks()
    assert "name=paged_read" not in text_g
    monkeypatch.setattr(PR, "MIN_BLOCK_BYTES", 0)
    got, cache_k, text_k = ticks()
    # the attention layer
    assert len(re.findall(r"jit\[\s*name=paged_read", text_k)) == 1
    for a, b in zip(got, want):
        np.testing.assert_allclose(a[[0, 1, 3]], b[[0, 1, 3]], atol=3e-2,
                                   rtol=3e-2)
        assert np.array_equal(a[[0, 1, 3]].argmax(-1), b[[0, 1, 3]].argmax(-1))
    # the first tick's K/V rows do not depend on the read
    for leaf in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_k[leaf][0], np.float32),
            np.asarray(cache_g[leaf][0], np.float32), atol=0)


def test_the_engine_says_how_it_reads(every_block, monkeypatch):
    """Through ``engine.serving``: the executor's report, ``health()``, the
    ready line, ``gathered_rows`` on every ``serve.decode`` span (live pages
    where the kernel reads, whole steps where the gather does), and the
    gather engine's tokens."""
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh
    from deepspeed_tpu.utils.logging import logger

    cfg = _olmo()
    engine = deepspeed_tpu.init_inference(
        model=CausalLM(cfg), params=init_params(cfg, jax.random.PRNGKey(1)),
        dtype="bf16",
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
            sv = engine.serving(b_slots=3, page_size=16, max_model_len=64)
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

    sv, want, attrs, ready = run()
    gather = {"k": "gather", "v": "gather"}
    assert sv._exec.mesh_info()["kv_read"] == gather
    assert sv.health()["kv_read"] == gather
    assert "kv_read=k:gather,v:gather" in ready
    # 3 slots x 2 pairs a step x 16 rows: whole steps
    assert {a["gathered_rows"] % 96 for a in attrs} == {0}
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    sv, got, attrs, ready = run()
    pages = {"k": "pages", "v": "pages"}
    assert sv._exec.mesh_info()["kv_read"] == pages
    assert sv.health()["kv_read"] == pages
    assert "kv_read=k:pages,v:pages" in ready
    assert any(a["gathered_rows"] % 96 for a in attrs)
    assert all(a["gathered_rows"] % 16 == 0
               and a["live_rows"] <= a["gathered_rows"] < a["live_rows"]
               + 16 * 3 for a in attrs)
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
