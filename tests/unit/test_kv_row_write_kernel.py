"""A decode token's K/V row stored where it lies (``ops/pallas/kv_row_write
.py``) against ``_merge_pages`` under the same plan, in interpret mode asked
for by name, and the rule that chooses between them (``kv_write_path``): the
kernel for one token a slot into a row-major K or V leaf whose shape its tile
plan takes, where a program may hold a kernel at all; the page merge
elsewhere."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.mixers import common as MX
from deepspeed_tpu.ops.pallas.kv_row_write import kv_row_write, row_block

PAGE, SLOTS, MAXP = 8, 6, 3
# what each slot is: live at a page's first row, live in the middle of its
# second page, live at a page's LAST row, masked, past its page table (3
# pages of 8 rows hold positions 0 .. 23), idle with nothing to write
START = jnp.array([0, 12, 15, 5, 24, 0], jnp.int32)
MASK = jnp.array([[True], [True], [True], [False], [True], [False]])
# the slots that keep their row and the row each keeps; a ring has no end,
# position 24 is row 0 of its logical page 3
KEPT = {"paged": {0: 0, 1: 4, 2: 7}, "ring": {0: 0, 1: 4, 2: 7, 4: 0}}
TABLE = jnp.arange(1, 1 + SLOTS * MAXP, dtype=jnp.int32).reshape(SLOTS, MAXP)
PAGES = 1 + SLOTS * MAXP            # a layer's, the trash page counted in


def _bits(a):
    return np.asarray(a).view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _plan(kind):
    if kind == "paged":
        return T._paged_write_plan(TABLE, START, MASK, PAGE)
    # a ring of two pages a slot: logical page j in ring page j % 2
    return T._ring_write_plan(TABLE[:, :2], START, MASK, PAGE)


@pytest.mark.parametrize("plan", ["paged", "ring"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", [16, 4, 8])
def test_kernel_leaves_the_leaf_as_the_page_merge_does(heads, dtype, plan,
                                                        monkeypatch):
    """Three layers' pages in one leaf, the write into the middle layer's
    (``pool_first``'s offset): bit for bit the merge's leaf, the trash pages
    and the other layers' pages included."""
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    write = T._plan_at(_plan(plan), PAGES)
    src, keep, pages, row = write
    kept = KEPT[plan]
    assert [kept.get(b, -1) for b in range(SLOTS)] == list(np.asarray(row))
    # a slot that keeps no row names the trash page and writes nothing
    assert all((b in kept) == (p != PAGES)
               for b, p in enumerate(np.asarray(pages[:, 0])))
    ks = jax.random.split(jax.random.PRNGKey(heads), 2)
    leaf = jax.random.normal(ks[0], (3 * PAGES, PAGE, heads, 128)
                             ).astype(dtype)
    new = jax.random.normal(ks[1], (SLOTS, 1, heads, 128), jnp.float32)
    want = T._merge_pages(leaf, new, write)
    got = jax.jit(lambda a, n: kv_row_write(
        a, n[:, 0].astype(a.dtype), pages[:, 0], row, interpret=True))(
            leaf, new)
    assert got.dtype == want.dtype and np.array_equal(_bits(got), _bits(want))
    changed = np.unique(np.nonzero(_bits(got) != _bits(leaf))[0])
    assert sorted(changed) == sorted(np.asarray(pages[:, 0])[list(kept)])


REFUSED = {
    "a-64-wide-head": ((40, PAGE, 32, 64), jnp.bfloat16),
    "a-192-wide-head": ((40, PAGE, 8, 192), jnp.bfloat16),
    "an-int8-pools-rows": ((40, PAGE, 16, 128), jnp.int8),
    "a-scale-plane": ((40, PAGE), jnp.float32),
    "a-latent-leaf-with-no-head-axis": ((40, PAGE, 576), jnp.bfloat16),
    # what Mosaic refuses to slice out of a tile, or to take at all (the
    # shapes it compiles: test_chip_bringup.py)
    "one-head": ((40, PAGE, 1, 128), jnp.bfloat16),
    "three-heads": ((40, PAGE, 3, 128), jnp.float32),
    "twelve-heads": ((40, PAGE, 12, 128), jnp.bfloat16),
    "float16": ((40, PAGE, 16, 128), jnp.float16),
}


@pytest.mark.parametrize("shape", list(REFUSED))
def test_a_shape_the_tile_plan_refuses_keeps_the_merge(shape, monkeypatch):
    """The kernel raises, and the rule never reaches it."""
    dims, dtype = REFUSED[shape]
    assert row_block(dims, dtype) is None
    assert row_block((40, PAGE, 16, 128), jnp.bfloat16) == (16, 128)
    with pytest.raises(NotImplementedError, match="no tile plan"):
        kv_row_write(jnp.zeros(dims, dtype), jnp.zeros((2,) + dims[2:], dtype),
                     jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
                     interpret=True)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    assert T.kv_write_path(jax.ShapeDtypeStruct(dims, dtype), None) == "page"


def _leaf(heads=16, width=128, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((40, PAGE, heads, width), dtype)


RULE = {
    "a-decode-tick-where-a-kernel-may-run": (
        dict(leaf=_leaf(), order=None), True, "row"),
    "a-decode-tick-compiled-for-the-chip": (
        dict(leaf=_leaf(4, 256, jnp.float32), order=None), False, "row"),
    "a-prompts-or-a-verify-block": (
        dict(leaf=_leaf(), order=None, tokens=5), True, "page"),
    "a-leaf-stored-page-rows-minor": (
        dict(leaf=_leaf(), order=(0, 1, 3, 4, 2)), True, "page"),
    "a-leaf-kept-head-major": (
        dict(leaf=_leaf(4), order=(0, 1, 3, 2, 4)), True, "page"),
    "a-backend-that-is-not-a-tpu": (
        dict(leaf=_leaf(), order=None), None, "page"),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_reads_what_the_trace_can_observe(case, monkeypatch):
    kw, interpret, want = RULE[case]
    if interpret is not None:
        monkeypatch.setattr(MX, "_pallas_interpret", lambda: interpret)
    assert T.kv_write_path(**kw) == want
    # the plan carries the kept row of every block of one token a slot,
    # whatever the backend: the rule above is the one gate
    assert _plan("paged")[3].shape == _plan("ring")[3].shape == (SLOTS,)
    assert T._paged_write_plan(TABLE, START, jnp.tile(MASK, (1, 5)),
                               PAGE)[3] is None


def test_a_sharded_mesh_keeps_the_merge(monkeypatch):
    """``pallas_call`` has no partitioning rule: the rule asks the mesh."""
    from deepspeed_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", None)
    assert T.kv_write_path(_leaf(), None) == "row"
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH",
                        mesh_mod.build_mesh(mesh_mod.MeshLayout(dp=2),
                                            jax.devices()[:2]))
    assert T.kv_write_path(_leaf(), None) == "page"


CACHES = {
    # model, overrides, init_paged_cache's kw, the order observed -> paths
    "k-and-v-of-whole-lanes": (
        "ouro-2.6b", dict(num_heads=2, num_kv_heads=2, head_dim=128), {},
        None, {"k": "row", "v": "row"}),
    "the-scale-planes-and-int8-rows": (
        "ouro-2.6b", dict(num_heads=2, num_kv_heads=2, head_dim=128),
        dict(kv_dtype="int8"), None,
        {"k": "page", "v": "page", "k_scale": "page", "v_scale": "page"}),
    "the-latent-leaf": (
        "kanana-2-30b-a3b", dict(
            num_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
            kv_lora_rank=120, num_experts=16, moe_experts_held=4,
            moe_top_k=3, moe_intermediate_size=32), {}, None,
        {"latent": "page"}),
    "an-order-a-leaf": (
        "ouro-2.6b", dict(num_heads=2, num_kv_heads=2, head_dim=128), {},
        {"k": (0, 1, 3, 4, 2), "v": None}, {"k": "page", "v": "row"}),
    "head-major-leaves-beside-a-state": (
        "falcon-h1-34b", dict(
            num_heads=4, num_kv_heads=2, head_dim=128, ssm_heads=4,
            ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=8), {},
        None, {"k": "page", "v": "page"}),
    "two-kinds-of-layer": (
        "mimo-v2.5", dict(
            num_layers=7, num_heads=8, num_kv_heads=2, window_kv_heads=8,
            head_dim=192, v_head_dim=128, rotary_dim=8, window_size=16,
            num_experts=16, moe_experts_held=4, moe_top_k=3,
            moe_intermediate_size=32), {},
        {"k": (0, 1, 3, 4, 2), "v": None, "k_window": (0, 1, 3, 4, 2),
         "v_window": None},
        # 2 x 128 values of the full layers are kept head-major
        {"k": "page", "v": "page", "k_window": "page", "v_window": "row"}),
}


@pytest.mark.parametrize("case", list(CACHES))
def test_every_leaf_of_a_cache_says_its_path(case, monkeypatch):
    name, over, cache_kw, order, want = CACHES[case]
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    cfg = get_config(name, **{**dict(
        num_layers=2, hidden_size=64, intermediate_size=96, vocab_size=256,
        max_seq_len=512), **over})
    cache = jax.eval_shape(lambda: T.init_paged_cache(
        cfg, 9, PAGE, dtype=jnp.bfloat16, **cache_kw))
    assert T.kv_write_paths(cfg, cache, order) == want
    # what the executor reports is what the trace holds: a kernel for every
    # leaf said to write by row (one in a layer scan's body, one a layer
    # where the forward walks the layers), none for any other
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, cfg.dtype),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    traced = str(jax.make_jaxpr(lambda p, c: T.forward_paged(
        cfg, p, jnp.zeros((2, 1), jnp.int32), c,
        jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4),
        jnp.array([3, 9], jnp.int32), jnp.ones((2, 1), bool),
        pool_order=order))(params, cache))
    walked = ({kind: n for kind, (_, n) in T.kind_layers(cfg).items()}
              if T.is_hybrid(cfg) else {})
    assert traced.count("name=kv_row_write") == sum(
        walked.get("window" if leaf.endswith("_window") else "full", 1)
        for leaf, path in want.items() if path == "row")
    # nowhere but on a TPU: every leaf a page at a time
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: None)
    assert set(T.kv_write_paths(cfg, cache, order).values()) == {"page"}


def _tiny(name):
    kw = dict(num_layers=2, hidden_size=64, intermediate_size=96,
              num_heads=2, num_kv_heads=2, head_dim=128, vocab_size=256,
              max_seq_len=512, dtype=jnp.float32)
    if name == "olmoe-1b-7b":
        kw.update(intermediate_size=32, num_experts=8, moe_top_k=3)
    return get_config(name, **kw)


@pytest.mark.parametrize("name", ["ouro-2.6b", "olmoe-1b-7b"])
def test_a_decode_tick_through_forward_paged_is_the_xla_ticks(name,
                                                              monkeypatch):
    """A prompt's block (the page merge either way) and two ticks of
    ``forward_paged`` over four slots, one masked and one past its table,
    with the kernel in the layer scan (and, for the looped model, in the scan
    of passes around it): logits and pool as the merge's program leaves them,
    bit for bit."""
    cfg = _tiny(name)
    params = init_params(cfg, jax.random.PRNGKey(2))
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(4, 2)
    mask = jnp.array([[True], [True], [False], [True]])

    def ticks():
        cache = T.init_paged_cache(cfg, 9, PAGE, dtype=jnp.float32)
        step = jax.jit(lambda c, t, s, m: T.forward_paged(
            cfg, params, t, c, table, s, m))
        toks = jax.random.randint(jax.random.PRNGKey(3), (4, 5), 0, 256)
        logits, cache = step(cache, toks, jnp.zeros((4,), jnp.int32),
                             jnp.ones((4, 5), bool))
        outs = [np.asarray(logits)]
        for start in ([5, 5, 5, 16], [6, 6, 5, 17]):
            logits, cache = step(cache, toks[:, :1] + start[0],
                                 jnp.asarray(start, jnp.int32), mask)
            outs.append(np.asarray(logits))
        traced = str(jax.make_jaxpr(lambda c: T.forward_paged(
            cfg, params, toks[:, :1], c, table,
            jnp.zeros((4,), jnp.int32), mask))(cache))
        return outs, cache, traced

    want, cache_x, text_x = ticks()
    assert "kv_row_write" not in text_x
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    got, cache_k, text_k = ticks()
    assert text_k.count("name=kv_row_write") == 2         # K and V, a layer
    for a, b in zip(got, want):
        assert np.array_equal(a[[0, 1]], b[[0, 1]])
    for leaf in ("k", "v"):
        assert np.array_equal(np.asarray(cache_k[leaf]),
                              np.asarray(cache_x[leaf]))


def test_the_engine_says_which_leaves_write_by_row(monkeypatch):
    """Through ``engine.serving``: the executor's report, the attrs on every
    ``serve.decode`` span, and the same tokens as the merge's engine."""
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    cfg = _tiny("ouro-2.6b")
    engine = deepspeed_tpu.init_inference(
        model=CausalLM(cfg), params=init_params(cfg, jax.random.PRNGKey(1)),
        dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=f"r{i}", arrival_time=0.0, max_new_tokens=6,
                    input_ids=rng.integers(0, 256, (5 + 3 * i,)
                                           ).astype(np.int32))
            for i in range(5)]

    def run():
        sv = engine.serving(b_slots=3, page_size=PAGE, max_model_len=32)
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
        return sv, out, {(a["kv_row_write_leaves"], a["kv_page_write_leaves"])
                         for a in attrs}

    sv, want, leaves = run()
    assert sv._exec.mesh_info()["kv_write"] == {"k": "page", "v": "page"}
    assert sv.health()["kv_write"] == {"k": "page", "v": "page"}
    assert leaves == {(0, 2)}
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    sv, got, leaves = run()
    assert sv._exec.mesh_info()["kv_write"] == {"k": "row", "v": "row"}
    assert leaves == {(2, 0)} and got == want
