"""The grouped product over the held groups' row tiles
(``ops/pallas/grouped_matmul.py``) against ``lax.ragged_dot`` on the same
operands, in interpret mode asked for by name; the rule that chooses between
them (``moe.sharded_moe.expert_matmul_path``): the kernel for a call whose
groups are many rows deep, in bfloat16, where a program may hold a kernel at
all and at a shape the tile plan takes, ``lax.ragged_dot`` elsewhere; and
``moe_ffn_nodrop`` end to end on either."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.mixers import common as MX
from deepspeed_tpu.moe import sharded_moe as M
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.observability import configure_tracer, get_tracer

K, N = 256, 128

# a decode tick of 128 slots x 4 experts a token over 32 experts, cut in
# width: gate / up as [512, 256] x [32, 256, 384], down as its transpose
TICK, TICK_DOWN = (256, 384), (384, 256)
# its group sizes: 16 rows each; one expert takes every row; three groups of
# one row; ends on either side of every tile edge (127 | 129, 255 | 257,
# 383 | 385: ROADMAP W3); a tick that drains, 40 live rows of 512
TICK_SIZES = {
    "sixteen-rows-a-group": [16] * 32,
    "one-group-takes-every-row": [0] * 7 + [512] + [0] * 24,
    "a-few-groups-of-one": [0] * 5 + [1] + [0] * 11 + [1, 1] + [0] * 13,
    "sizes-that-straddle-every-tile-edge": (
        [127, 2, 0, 126, 2, 0, 0, 126, 2] + [0] * 10 + [120] + [1] * 7
        + [0] * 5),
    "draining-40-rows-of-512": (
        [3, 0, 1, 0, 0, 2, 5, 0, 1, 1, 0, 0, 4, 0, 2, 1] + [0] * 8
        + [6, 0, 3, 0, 1, 2, 0, 8]),
}

# name: (m, group sizes, tiling or None for the plan's own[, (k, n)])
CASES = {
    "groups-that-end-inside-a-tile": (512, [100, 37, 200, 50], None),
    "groups-that-fill-their-tiles": (512, [128, 256, 128], None),
    "empty-groups-between-full-ones": (
        512, [0, 0, 0, 90, 0, 170, 60, 0, 0], None),
    "a-stacks-other-layers-before-and-after": (
        384, [0] * 8 + [40, 3, 0, 130, 77, 9, 1, 60] + [0] * 8, None),
    "one-row-a-group": (256, [1] * 16, None),
    "one-group-over-every-row": (384, [384], None),
    "every-row-dead": (256, [0, 0, 0, 0], None),
    "k-in-two-tiles": (512, [100, 37, 200, 50], (128, 128, 128)),
    "rows-in-tiles-of-256": (512, [100, 37, 200, 50], (256, 256, 128)),
    **{f"a-tick-{name}": (512, sizes, None, TICK)
       for name, sizes in TICK_SIZES.items()},
    **{f"a-ticks-down-{name}": (512, sizes, None, TICK_DOWN)
       for name, sizes in TICK_SIZES.items()},
}
assert all(len(s) == 32 for s in TICK_SIZES.values())
assert sum(TICK_SIZES["sizes-that-straddle-every-tile-edge"]) == 512
assert sum(TICK_SIZES["draining-40-rows-of-512"]) == 40


def _operands(m, sizes, dtype=jnp.bfloat16, seed=0, k=K, n=N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(ks[0], (m, k), jnp.float32)
    rhs = jax.random.normal(ks[1], (len(sizes), k, n), jnp.float32) * k ** -.5
    # the rows of no group hold NaN: nothing of them may reach a live row
    lhs = lhs.at[sum(sizes):].set(jnp.nan)
    return lhs.astype(dtype), rhs.astype(dtype), jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_ragged_dot_on_the_rows_of_a_group(case):
    m, sizes, tiling, *widths = CASES[case]
    k, n = widths[0] if widths else (K, N)
    lhs, rhs, gs = _operands(m, sizes, k=k, n=n)
    live = sum(sizes)
    got = jax.jit(lambda a, b, s: gm.grouped_matmul(a, b, s, tiling, True))(
        lhs, rhs, gs)
    assert got.shape == (m, n) and got.dtype == jnp.bfloat16
    want = jax.lax.ragged_dot(lhs, rhs, gs)
    # float32 sums of bfloat16 products, rounded once: the float32
    # reference's own rounding to bfloat16, and lax.ragged_dot's, to a unit
    # in the last place of a bfloat16
    exact = np.zeros((m, n), np.float32)
    row = 0
    for g, r in enumerate(sizes):
        exact[row:row + r] = (np.asarray(lhs[row:row + r], np.float32)
                              @ np.asarray(rhs[g], np.float32))
        row += r
    got32 = np.asarray(got, np.float32)[:live]
    assert np.isfinite(got32).all()
    np.testing.assert_allclose(got32, exact[:live], rtol=2 ** -7, atol=1e-2)
    np.testing.assert_allclose(
        got32, np.asarray(want, np.float32)[:live], rtol=2 ** -7, atol=1e-2)


def test_the_visits_are_megabloxs():
    """The (row tile, group) pairs the grid visits are those
    ``megablox.gmm`` visits, in its order."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata

    for m, sizes, *_ in CASES.values():
        gs = jnp.asarray(sizes, jnp.int32)
        (off, gid, tid), n = make_group_metadata(
            group_sizes=gs, m=m, tm=128, start_group=jnp.int32(0),
            num_nonzero_groups=len(sizes), visit_empty_groups=False)
        off2, gid2, tid2, n2 = gm.group_metadata(gs, m, 128)
        assert int(n) == int(n2)
        np.testing.assert_array_equal(off, off2)
        np.testing.assert_array_equal(gid[:int(n)], gid2[:int(n)])
        np.testing.assert_array_equal(tid[:int(n)], tid2[:int(n)])


@pytest.mark.parametrize("m,tiling,said", [
    (200, None, "row tile (128)"),
    (384, (256, 256, 128), "row tile (256)"),
])
def test_rows_that_are_not_whole_tiles_are_refused_by_name(m, tiling, said):
    lhs, rhs, gs = _operands(m, [m // 2, 8])
    with pytest.raises(NotImplementedError, match="row tile") as e:
        gm.grouped_matmul(lhs, rhs, gs, tiling, True)
    assert said in str(e.value) and "lax.ragged_dot" in str(e.value)


def test_float32_operands_sum_in_float32():
    m, sizes, _ = CASES["groups-that-end-inside-a-tile"]
    lhs, rhs, gs = _operands(m, sizes, jnp.float32)
    got = gm.grouped_matmul(lhs, rhs, gs, None, True)
    assert got.dtype == jnp.float32
    live = sum(sizes)
    want = jax.lax.ragged_dot(lhs, rhs, gs,
                              precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[:live], rtol=1e-5, atol=1e-5)


def test_the_gradients_are_ragged_dots():
    m, sizes, _ = CASES["empty-groups-between-full-ones"]
    lhs, rhs, gs = _operands(m, sizes, jnp.float32)
    lhs = jnp.nan_to_num(lhs)           # a gradient reads every row
    live = (jnp.arange(m) < sum(sizes))[:, None]
    tgt = jax.random.normal(jax.random.PRNGKey(3), (m, N), jnp.float32)

    def loss(product):
        return lambda a, b: jnp.sum(
            jnp.where(live, product(a, b), 0) * tgt)

    kernel = loss(lambda a, b: gm.grouped_matmul(a, b, gs, None, True))
    ragged = loss(lambda a, b: jax.lax.ragged_dot(
        a, b, gs, precision=jax.lax.Precision.HIGHEST))
    (v, (da, db)) = jax.value_and_grad(kernel, (0, 1))(lhs, rhs)
    (v0, (da0, db0)) = jax.value_and_grad(ragged, (0, 1))(lhs, rhs)
    np.testing.assert_allclose(v, v0, rtol=1e-5)
    np.testing.assert_allclose(da, da0, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(db, db0, rtol=1e-4, atol=1e-4)
    # under differentiation the rows of no group come out as zeros
    out = jax.vjp(lambda a: gm.grouped_matmul(a, rhs, gs, None, True),
                  lhs)[0]
    assert not np.asarray(out)[sum(sizes):].any()


# ------------------------------------------------------------------ the rule

D, F, E, TOPK = 128, 128, 8, 4


@pytest.mark.parametrize("case,kw,want", [
    ("a-prompts-chunk", dict(), "kernel"),
    # 32 slots x 4 experts a token are one whole tile of 128 rows: what
    # keeps a tick off the kernel is the depth of its groups, 2 rows over
    # 64 experts, and 16 rows over 8 are the kernel's
    ("a-ticks-few-rows", dict(tokens=32, num_experts=64), "ragged_dot"),
    ("a-ticks-sixteen-rows", dict(tokens=32), "kernel"),
    ("just-under-the-bound", dict(
        tokens=32, num_experts=128 // M.KERNEL_ROWS_AN_EXPERT + 1),
     "ragged_dot"),
    ("float32", dict(dtype=jnp.float32), "ragged_dot"),
    ("rows-not-whole-tiles", dict(tokens=200), "ragged_dot"),
    ("a-width-not-whole-lanes", dict(d_ff=96), "ragged_dot"),
    ("no-kernel-may-be-held", dict(interpret=None), "ragged_dot"),
])
def test_the_rule_reads_what_the_trace_can_observe(case, kw, want):
    kw = dict(kw)
    args = dict(tokens=256, top_k=TOPK, num_experts=E, d_model=D, d_ff=F,
                dtype=jnp.bfloat16,
                pallas_interpret=kw.pop("interpret", True))
    args.update(kw)
    assert M.expert_matmul_path(**args) == want


# the tick of every serving cell of the benchmark, the path chosen as on a TPU
# (the test answers for the backend): rows an expert by the router's width
# (OLMoE 16 x 8 / 64 = 2, MiMo 32 x 8 / 256 = 1, Kanana 32 x 6 / 128 = 1.5,
# Granite 32 x 10 / 72 = 4.4, LFM2 128 x 4 / 32 = 16), None without experts
CELL_TICKS = {
    "opt-1.3b.chat": None,
    "opt-1.3b.longprompt": None,
    "opt-1.3b.chat-backlog": None,
    "olmoe-1b-7b-d12.rollout-backlog": "ragged_dot",
    "mimo-v2.5-ep16-d7.reasoning-backlog": "ragged_dot",
    "kanana-2-30b-a3b-ep8-d24.longctx-backlog": "ragged_dot",
    "falcon-h1-34b-d5.chatburst-backlog": None,
    "ouro-2.6b.mathrollout-backlog": None,
    "granite-4.0-h-small-ep2-d10.ragdoc-backlog": "ragged_dot",
    "olmo-hybrid-7b-d16.thinkrollout-backlog": None,
    "lfm2-8b-a1b-d14.agentturn-backlog": "kernel",
}


@pytest.mark.parametrize("cell", list(CELL_TICKS))
def test_a_cells_tick_by_the_depth_of_its_groups(monkeypatch, cell):
    import json
    import os

    from benchmark.lib import system

    root = os.path.join(os.path.dirname(__file__), "..", "..")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == cell)
    with open(os.path.join(root, "benchmark", "traffic",
                           entry["traffic"] + ".json")) as f:
        slots = json.load(f)["engine"]["b_slots"]
    with open(os.path.join(root, "benchmark", "configs",
                           entry["config"] + ".json")) as f:
        cfg = system.transformer_config(json.load(f), False)
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    assert T.expert_matmul_path(cfg, slots, 1) == CELL_TICKS[cell]


def test_this_host_keeps_ragged_dot():
    """No TPU here and nobody answered in the backend's place: the model
    hands the layer ``None`` and the layer asks for no kernel of its own."""
    assert MX._pallas_interpret() is None
    assert T.expert_matmul_path(_tiny(), 1, 256) == "ragged_dot"
    assert M.expert_matmul_path(4096, TOPK, E, D, F,
                                jnp.bfloat16) == "ragged_dot"


# ------------------------------------------------- moe_ffn_nodrop end to end

MOE_CASES = {
    "softmax-every-expert-here": dict(),
    "sigmoid-a-held-share": dict(score_func="sigmoid", held=(2, 4)),
    "a-prompts-padding-masked": dict(mask=200),
    "a-stack-at-an-offset": dict(held=(4, 4), mask=230, stack=3, offset=4),
    "gelu-experts-with-biases": dict(activation="gelu", held=(0, 4)),
    # a decode tick of 128 slots, top 4 of 32 experts (16 rows an expert, the
    # bound): every third slot idle; and one that drains, nine slots live
    "a-tick-with-idle-slots": dict(tick=np.arange(128) % 3 != 0),
    "a-draining-tick": dict(tick=np.arange(128) % 15 == 4),
}


@pytest.mark.parametrize("case", list(MOE_CASES))
def test_the_expert_layer_on_the_kernel_is_the_parents(case):
    kw = dict(MOE_CASES[case])
    S, held = 256, kw.get("held")
    tick = kw.get("tick")
    n_exp = E if tick is None else 32
    here = held[1] if held else n_exp
    stack, offset = kw.get("stack", 1), kw.get("offset")
    act = kw.get("activation", "swiglu")
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    x = jax.random.normal(ks[0], (1, S, D) if tick is None
                          else (len(tick), 1, D), jnp.bfloat16)
    router = jax.random.normal(ks[1], (D, n_exp), jnp.float32)

    def leaf(key, *shape):
        return (jax.random.normal(key, (stack * here,) + shape) * 0.1
                ).astype(jnp.bfloat16)

    if act == "swiglu":
        p = {"w_gate": leaf(ks[2], D, F), "w_up": leaf(ks[3], D, F),
             "w_down": leaf(ks[4], F, D)}
    else:
        p = {"w_in": leaf(ks[2], D, F), "b_in": leaf(ks[3], F),
             "w_down": leaf(ks[4], F, D), "b_down": leaf(ks[5], D)}
    cfg = M.MoEConfig(num_experts=n_exp, top_k=TOPK, drop_tokens=False,
                      held=held, score_func=kw.get("score_func", "softmax"))
    mask = (jnp.arange(S)[None, :] < kw["mask"]) if "mask" in kw else None
    if tick is not None:
        # a tick is at the bound, neither side of the rule by its tiles
        assert len(tick) * TOPK == M.KERNEL_ROWS_AN_EXPERT * n_exp
        mask = jnp.asarray(tick)[:, None]

    def layer(interpret):
        return lambda x: M.moe_ffn_nodrop(
            x, router, p, cfg, activation=act, token_mask=mask,
            expert_offset=None if offset is None else jnp.int32(offset),
            pallas_interpret=interpret)

    assert "grouped_matmul" not in str(jax.make_jaxpr(layer(None))(x))
    y0, aux0, counts0 = jax.jit(layer(None))(x)
    assert str(jax.make_jaxpr(layer(True))(x)).count("grouped_matmul") >= (
        3 if act == "swiglu" else 2)
    y, aux, counts = jax.jit(layer(True))(x)
    np.testing.assert_array_equal(counts, counts0)
    assert float(aux) == float(aux0)
    y, y0 = np.asarray(y, np.float32), np.asarray(y0, np.float32)
    assert np.isfinite(y).all()
    # the same float32 sums rounded to bfloat16 at the same places: what
    # differs is the order of a sum's terms
    np.testing.assert_allclose(y, y0, rtol=2 ** -6, atol=2 ** -6)
    if mask is not None:
        real = np.asarray(mask)
        assert not y[~real].any() and y[real].any()
        if held is None:    # no row dropped: every real token's every pair
            assert int(counts.sum()) == int(real.sum()) * TOPK


# --------------------------------------------- what the executor reports

def _tiny(**over):
    kw = dict(num_layers=2, hidden_size=D, intermediate_size=F, num_heads=4,
              vocab_size=256, num_experts=E, moe_top_k=TOPK, max_seq_len=512,
              dtype=jnp.bfloat16)
    kw.update(over)
    return get_config("olmoe-1b-7b", **kw)


def test_the_executor_names_the_path_of_every_compiled_program(monkeypatch):
    """``mesh_info()["expert_matmul"]`` and the spans' two counters say of
    each program what its trace holds: a 256-token prompt (128 rows an
    expert) the kernel, a tick of three slots ``lax.ragged_dot``."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    monkeypatch.setattr(T, "kv_write_path", lambda *a, **k: "page")
    cfg = _tiny()
    mesh = initialize_mesh(MeshLayout(), devices=jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        model=CausalLM(cfg), params=init_params(cfg, jax.random.PRNGKey(0)),
        dtype="bf16", mesh=mesh)
    sv = engine.serving(b_slots=3, page_size=16, max_model_len=320)
    assert sv._exec.mesh_info()["expert_matmul"] == {"decode": "ragged_dot"}
    get_tracer().reset()        # another test's spans are not this run's
    configure_tracer(enabled=True)
    try:
        rng = np.random.default_rng(0)
        sv.run([Request(rid="long", max_new_tokens=3,
                        input_ids=rng.integers(0, 256, 250, dtype=np.int32)),
                Request(rid="short", max_new_tokens=3,
                        input_ids=rng.integers(0, 256, 9, dtype=np.int32))])
        spans = get_tracer().recorder.snapshot()
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    said = sv._exec.mesh_info()["expert_matmul"]
    assert said == sv.health()["expert_matmul"] == {
        "decode": "ragged_dot", "prefill_16": "ragged_dot",
        "prefill_256": "kernel"}
    by = {}
    for s in spans:
        if s.name in ("serve.prefill", "serve.decode"):
            by.setdefault((s.name, s.attrs.get("bucket")), s.attrs)
    assert by[("serve.prefill", 256)]["moe_kernel_products"] == 6
    assert by[("serve.prefill", 256)]["moe_ragged_products"] == 0
    assert by[("serve.prefill", 16)]["moe_kernel_products"] == 0
    assert by[("serve.prefill", 16)]["moe_ragged_products"] == 6
    decode = next(a for (n, _), a in by.items() if n == "serve.decode")
    assert (decode["moe_kernel_products"], decode["moe_ragged_products"]) \
        == (0, 6)
    # the rows sorted and the rows the way in moved (PR 50): the kernel's
    # program fills its live rows in whole tiles, a layer; every other
    # program gathers every sorted row, padding and idle slots included
    from deepspeed_tpu.moe import live_rows
    long = by[("serve.prefill", 256)]
    assert long["moe_sorted_rows"] == 2 * 256 * TOPK
    assert long["moe_rows"] == 2 * 250 * TOPK
    assert long["moe_rows"] <= long["moe_moved_rows"] == 2 * \
        live_rows.moved_rows(250 * TOPK, 256 * TOPK) <= long["moe_sorted_rows"]
    short = by[("serve.prefill", 16)]
    assert short["moe_moved_rows"] == short["moe_sorted_rows"] \
        == 2 * 16 * TOPK > short["moe_rows"]
    assert decode["moe_moved_rows"] == decode["moe_sorted_rows"] \
        == 2 * 3 * TOPK + 2 * (-(3 * TOPK) % 8)
    # and the traces hold what was said of them
    ex = sv._exec
    for program, path in said.items():
        if program == "decode":
            continue
        s_pad = int(program.split("_")[1])
        text = str(jax.make_jaxpr(
            lambda p, t: ex.model.apply_paged(
                p, t, T.paged_pool_cache(ex.pools, ex._pool_keys),
                jnp.zeros((1, ex.pages_per_slot), jnp.int32),
                jnp.zeros((1,), jnp.int32), jnp.ones((1, s_pad), bool),
                pool_order=ex.pool_order))(
            ex.params, jnp.zeros((1, s_pad), jnp.int32)))
        assert (text.count("grouped_matmul") > 0) == (path == "kernel")


def test_a_tick_on_the_kernel_emits_the_plain_engines_tokens(monkeypatch):
    """A decode tick of 32 slots, top 4 of 8 experts (16 rows an expert: the
    bound), through the serving loop on each side of the rule: the kernel's
    engine says so of its tick, its spans count six kernel products, and its
    streams are the plain engine's, through the first fill, full ticks and
    the drain (idle slots are rows of no group)."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    monkeypatch.setattr(T, "kv_write_path", lambda *a, **k: "page")
    cfg = _tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = initialize_mesh(MeshLayout(), devices=jax.devices()[:1])
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, int(n), dtype=np.int32)
               for n in rng.integers(3, 14, 40)]
    new = rng.integers(2, 9, 40)

    def streams(interpret):
        monkeypatch.setattr(MX, "_pallas_interpret", lambda: interpret)
        engine = deepspeed_tpu.init_inference(
            model=CausalLM(cfg), params=params, dtype="bf16", mesh=mesh)
        sv = engine.serving(b_slots=32, page_size=16, max_model_len=32)
        get_tracer().reset()
        configure_tracer(enabled=True)
        try:
            out = sv.run([Request(rid=f"r{i}", input_ids=p,
                                  max_new_tokens=int(n))
                          for i, (p, n) in enumerate(zip(prompts, new))])
            ticks = [s.attrs for s in get_tracer().recorder.snapshot()
                     if s.name == "serve.decode"]
        finally:
            configure_tracer(enabled=False)
            get_tracer().reset()
        return (sv._exec.mesh_info()["expert_matmul"]["decode"], ticks,
                {r.rid: list(r.output_ids) for r in out})

    path, ticks, got = streams(True)
    assert path == "kernel" and len(ticks) > 8
    assert {(a["moe_kernel_products"], a["moe_ragged_products"])
            for a in ticks} == {(6, 0)}
    # full ticks and draining ones, every live pair in some group
    assert max(a["moe_rows"] for a in ticks) == 2 * 32 * TOPK
    assert min(a["moe_rows"] for a in ticks) < 2 * 8 * TOPK
    path0, ticks0, want = streams(None)
    assert path0 == "ragged_dot"
    assert {(a["moe_kernel_products"], a["moe_ragged_products"])
            for a in ticks0} == {(0, 6)}
    assert got == want
