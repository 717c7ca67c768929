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
from deepspeed_tpu.moe import sharded_moe as M
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.observability import configure_tracer, get_tracer

K, N = 256, 128

# name: (m, group sizes, tiling or None for the plan's own)
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
}


def _operands(m, sizes, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(ks[0], (m, K), jnp.float32)
    rhs = jax.random.normal(ks[1], (len(sizes), K, N), jnp.float32) * K ** -.5
    # the rows of no group hold NaN: nothing of them may reach a live row
    lhs = lhs.at[sum(sizes):].set(jnp.nan)
    return lhs.astype(dtype), rhs.astype(dtype), jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_ragged_dot_on_the_rows_of_a_group(case):
    m, sizes, tiling = CASES[case]
    lhs, rhs, gs = _operands(m, sizes)
    live = sum(sizes)
    got = jax.jit(lambda a, b, s: gm.grouped_matmul(a, b, s, tiling, True))(
        lhs, rhs, gs)
    assert got.shape == (m, N) and got.dtype == jnp.bfloat16
    want = jax.lax.ragged_dot(lhs, rhs, gs)
    # float32 sums of bfloat16 products, rounded once: the float32
    # reference's own rounding to bfloat16, and lax.ragged_dot's, to a unit
    # in the last place of a bfloat16
    exact = np.zeros((m, N), np.float32)
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

    for m, sizes, _ in CASES.values():
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
    ("a-ticks-few-rows", dict(tokens=8), "ragged_dot"),
    ("just-under-the-bound", dict(
        tokens=M.KERNEL_ROWS_AN_EXPERT * E // TOPK - 32), "ragged_dot"),
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


def test_this_host_keeps_ragged_dot():
    """No TPU here and nobody answered in the backend's place: the model
    hands the layer ``None`` and the layer asks for no kernel of its own."""
    assert T._pallas_interpret() is None
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
}


@pytest.mark.parametrize("case", list(MOE_CASES))
def test_the_expert_layer_on_the_kernel_is_the_parents(case):
    kw = dict(MOE_CASES[case])
    S, held = 256, kw.get("held")
    here = held[1] if held else E
    stack, offset = kw.get("stack", 1), kw.get("offset")
    act = kw.get("activation", "swiglu")
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    x = jax.random.normal(ks[0], (1, S, D), jnp.bfloat16)
    router = jax.random.normal(ks[1], (D, E), jnp.float32)

    def leaf(key, *shape):
        return (jax.random.normal(key, (stack * here,) + shape) * 0.1
                ).astype(jnp.bfloat16)

    if act == "swiglu":
        p = {"w_gate": leaf(ks[2], D, F), "w_up": leaf(ks[3], D, F),
             "w_down": leaf(ks[4], F, D)}
    else:
        p = {"w_in": leaf(ks[2], D, F), "b_in": leaf(ks[3], F),
             "w_down": leaf(ks[4], F, D), "b_down": leaf(ks[5], D)}
    cfg = M.MoEConfig(num_experts=E, top_k=TOPK, drop_tokens=False,
                      held=held, score_func=kw.get("score_func", "softmax"))
    mask = (jnp.arange(S)[None, :] < kw["mask"]) if "mask" in kw else None

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
        assert not y[0, kw["mask"]:].any()


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

    monkeypatch.setattr(T, "_pallas_interpret", lambda: True)
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
