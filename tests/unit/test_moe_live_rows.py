"""The sorted rows' way in and way back over the live rows alone
(``moe/live_rows.py``) against what ``moe_ffn_nodrop`` does on the
``lax.ragged_dot`` side of its rule (every row gathered in, a select over
every row, a gather back and a float32 sum) on the same inputs, in interpret
mode asked for by name.  Everything a kernel or the fill leaves unwritten is
poisoned with NaN first: nothing of a row in no group may reach a result."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import live_rows
from deepspeed_tpu.moe import sharded_moe as M
from deepspeed_tpu.ops.pallas import grouped_matmul as gm

D, F, E, TOPK, S = 128, 128, 16, 4, 256

HELD = {"every-expert-here": None, "a-half-held": (4, 8),
        "a-sixteenth-held": (5, 1)}
MASKS = {"no-mask": None, "a-ragged-tail": 203, "all-padding": 0}
OPTIONS = {
    "as-published": dict(),
    "a-stack-at-an-offset": dict(stack=3, offset=1),
    "a-selection-bias": dict(select_bias=True),
    "gates-as-they-are": dict(norm_topk_prob=False),
    "gates-scaled": dict(routed_scale=2.5),
    "sigmoid-scores": dict(score_func="sigmoid"),
}
LAYER_CASES = (
    [(h, m, "as-published") for h in HELD for m in MASKS]
    + [("a-half-held", "a-ragged-tail", o) for o in list(OPTIONS)[1:]])


@pytest.fixture
def poisoned(monkeypatch):
    """NaN wherever the change leaves memory unwritten: the buffer the way
    in fills, and the rows past the last group of every product."""
    monkeypatch.setattr(
        live_rows, "_unwritten",
        lambda shape, dtype, interpret: jnp.full(shape, jnp.nan, dtype))
    product = gm.grouped_matmul

    def poisoning(lhs, rhs, group_sizes, *a):
        dead = jnp.arange(lhs.shape[0])[:, None] >= group_sizes.sum()
        return jnp.where(dead, jnp.nan, product(lhs, rhs, group_sizes, *a))

    monkeypatch.setattr(gm, "grouped_matmul", poisoning)


def _layer(held, mask, options):
    kw = dict(OPTIONS[options])
    held = HELD[held]
    here = held[1] if held else E
    stack, offset = kw.pop("stack", 1), kw.pop("offset", None)
    bias = kw.pop("select_bias", False)
    ks = jax.random.split(jax.random.PRNGKey(50), 6)
    x = jax.random.normal(ks[0], (1, S, D), jnp.bfloat16)
    router = jax.random.normal(ks[1], (D, E), jnp.float32)
    p = {n: (jax.random.normal(k, (stack * here,) + shape) * 0.1
             ).astype(jnp.bfloat16)
         for n, k, shape in (("w_gate", ks[2], (D, F)), ("w_up", ks[3], (D, F)),
                             ("w_down", ks[4], (F, D)))}
    cfg = M.MoEConfig(num_experts=E, top_k=TOPK, drop_tokens=False,
                      held=held, **kw)
    n_real = MASKS[mask]
    token_mask = None if n_real is None else jnp.arange(S)[None, :] < n_real

    def layer(interpret):
        return lambda x, router, p: M.moe_ffn_nodrop(
            x, router, p, cfg, token_mask=token_mask,
            expert_offset=None if offset is None else jnp.int32(offset * here),
            select_bias=(jnp.linspace(-1, 1, E) if bias else None),
            pallas_interpret=interpret)

    return layer, (x, router, p), n_real


@pytest.mark.parametrize("held,mask,options", LAYER_CASES)
def test_the_layer_over_live_rows_is_the_plain_forms(poisoned, held, mask,
                                                     options):
    layer, args, n_real = _layer(held, mask, options)
    assert "rows_in" not in str(jax.make_jaxpr(layer(None))(*args))
    y0, aux0, counts0 = jax.jit(layer(None))(*args)
    text = str(jax.make_jaxpr(layer(True))(*args))
    assert "rows_in" in text and "rows_back" in text
    y, aux, counts = jax.jit(layer(True))(*args)
    np.testing.assert_array_equal(counts, counts0)
    assert float(aux) == float(aux0)
    y, y0 = np.asarray(y, np.float32), np.asarray(y0, np.float32)
    assert np.isfinite(y).all()
    # the same float32 sums rounded to bfloat16 at the same places
    np.testing.assert_allclose(y, y0, rtol=2 ** -6, atol=2 ** -6)
    if n_real is not None:
        assert not y[0, n_real:].any()
        assert y[0, :n_real].any() == (n_real > 0)


# ------------------------------------------------------- the two ways alone

# name: (tokens, top_k, experts, held, real tokens, rows a step in, tokens a
# step back): tiles that divide the rows, that do not, and that pass them
ROUTINGS = {
    "whole-tiles": (64, 4, 8, 4, 64, 64, 16),
    "tiles-that-do-not-divide": (64, 4, 8, 4, 50, 48, 24),
    "a-tile-over-every-row": (64, 4, 8, 8, 64, 512, 256),
    "one-held-of-sixteen": (64, 4, 16, 1, 37, 32, 8),
    "no-live-row-real-tokens": (64, 4, 8, 0, 64, 32, 16),
    "all-padding": (64, 4, 8, 4, 0, 32, 16),
}


def _routing(case, seed=0):
    T, k, experts, held, real, _, _ = ROUTINGS[case]
    rng = np.random.default_rng(seed)
    idx = np.argsort(rng.random((T, experts)), axis=1)[:, :k]
    flat = np.where(idx < held, idx, held)
    flat = np.where((np.arange(T) < real)[:, None], flat, held).reshape(-1)
    order = np.argsort(flat, kind="stable")
    n_live = int((flat < held).sum())
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (T, D), jnp.bfloat16)
    out = jax.random.normal(ks[1], (T * k, D), jnp.bfloat16)
    out = out.at[n_live:].set(jnp.nan)      # nobody wrote the rows past
    gates = jax.random.uniform(ks[2], (T, k), jnp.float32)
    return dict(x=x, out=out, gates=gates, n_live=n_live, real=real,
                tok=jnp.asarray(np.minimum(order // k, T - 1), jnp.int32),
                inv=jnp.asarray(np.argsort(order).reshape(T, k), jnp.int32),
                live=jnp.asarray(np.sort(flat) < held))


def _plain_back(out, live, inv, gates):
    """``moe_ffn_nodrop``'s way back on the ``lax.ragged_dot`` side."""
    T, k = inv.shape
    got = jnp.where(live[:, None], out, 0)[inv.reshape(-1)]
    return jnp.sum(got.reshape(T, k, -1).astype(jnp.float32)
                   * gates[:, :, None], axis=1).astype(out.dtype)


@pytest.fixture
def tiles(monkeypatch):
    def set_tiles(case):
        monkeypatch.setattr(live_rows, "ROWS_IN_TILE", ROUTINGS[case][5])
        monkeypatch.setattr(live_rows, "TOKENS_BACK_TILE", ROUTINGS[case][6])
    return set_tiles


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_the_way_in_fills_the_live_rows_and_whole_tiles_alone(
        monkeypatch, tiles, case):
    tiles(case)
    monkeypatch.setattr(
        live_rows, "_unwritten",
        lambda shape, dtype, interpret: jnp.full(shape, jnp.nan, dtype))
    r = _routing(case)
    xs = np.asarray(live_rows._rows_in(r["x"], r["tok"], r["n_live"], True),
                    np.float32)
    want = np.asarray(r["x"][r["tok"]], np.float32)
    moved = int(live_rows.moved_rows(r["n_live"], len(want)))
    assert r["n_live"] <= moved <= len(want)
    np.testing.assert_array_equal(xs[:r["n_live"]], want[:r["n_live"]])
    # at most the last rows of a row count that is no whole number of tiles
    # are filled beside them; past those nothing is
    filled = np.isfinite(xs).all(axis=1)
    assert filled[:r["n_live"]].all() and filled.sum() <= moved + (
        -len(want) % min(ROUTINGS[case][5], len(want)))
    np.testing.assert_array_equal(xs[filled], want[filled])


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_the_way_back_never_reads_a_row_past_the_last_group(tiles, case):
    tiles(case)
    r = _routing(case)
    y = np.asarray(live_rows._rows_back(
        r["out"], r["inv"], r["gates"], r["n_live"], r["real"]), np.float32)
    assert np.isfinite(y).all()
    want = np.asarray(_plain_back(r["out"], r["live"], r["inv"], r["gates"]),
                      np.float32)
    # a tile's sum and the whole array's are the same float32 sum a token
    np.testing.assert_allclose(y, want, rtol=2 ** -7, atol=2 ** -7)
    assert not y[r["real"]:].any()


def test_the_unwritten_buffer_is_a_kernel_with_no_body():
    buf = live_rows._unwritten((256, D), jnp.bfloat16, True)
    assert buf.shape == (256, D) and buf.dtype == jnp.bfloat16
    text = str(jax.make_jaxpr(
        lambda: live_rows._unwritten((256, D), jnp.bfloat16, True))())
    assert "pallas_call" in text and "broadcast" not in text


@pytest.mark.parametrize("case", ["tiles-that-do-not-divide",
                                  "one-held-of-sixteen", "all-padding"])
def test_the_gradients_are_the_plain_forms(tiles, case):
    tiles(case)
    r = _routing(case)
    out = jnp.nan_to_num(r["out"]).astype(jnp.float32)
    x = r["x"].astype(jnp.float32)
    tgt_in = jax.random.normal(jax.random.PRNGKey(7), (len(r["tok"]), D))
    tgt_back = jax.random.normal(jax.random.PRNGKey(8), x.shape)
    live_row = (jnp.arange(len(r["tok"])) < r["n_live"])[:, None]

    def way_in(rows_in):
        return lambda x: jnp.sum(jnp.where(live_row, rows_in(x), 0) * tgt_in)

    dx = jax.grad(way_in(lambda x: live_rows.rows_in(
        x, r["tok"], r["n_live"], True)))(x)
    dx0 = jax.grad(way_in(lambda x: x[r["tok"]]))(x)
    np.testing.assert_allclose(dx, dx0, rtol=1e-5, atol=1e-5)
    # under differentiation the rows of no group come in as zeros
    xs = jax.vjp(lambda x: live_rows.rows_in(x, r["tok"], r["n_live"], True),
                 x)[0]
    assert not np.asarray(xs)[r["n_live"]:].any()

    def way_back(rows_back):
        return lambda o, g: jnp.sum(rows_back(o, g) * tgt_back)

    d = jax.grad(way_back(lambda o, g: live_rows.rows_back(
        o, r["inv"], g, r["n_live"], r["real"])), (0, 1))(out, r["gates"])
    d0 = jax.grad(way_back(lambda o, g: _plain_back(
        o, r["live"], r["inv"], g)), (0, 1))(out, r["gates"])
    for got, want in zip(d, d0):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_layers_gradients_are_the_plain_forms(poisoned):
    layer, (x, router, p), _ = _layer("a-half-held", "a-ragged-tail",
                                      "as-published")
    tgt = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)

    def loss(interpret):
        def f(x, router, p):
            y, aux, _ = layer(interpret)(x, router, p)
            return jnp.sum(y.astype(jnp.float32) * tgt) + aux
        return f

    g = jax.grad(loss(True), (0, 1, 2))(x, router, p)
    g0 = jax.grad(loss(None), (0, 1, 2))(x, router, p)
    for got, want in zip(jax.tree_util.tree_leaves(g),
                         jax.tree_util.tree_leaves(g0)):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=2 ** -5,
                                   atol=2 ** -5 * np.abs(want).max())


@pytest.mark.parametrize("bucket,live_tokens,chunks", [
    (2048, 1500, 1), (8192, 8192, 4), (8192, 5000, 3), (8192, 2048, 1)])
def test_the_moved_rows_of_a_program_from_its_counts(monkeypatch, bucket,
                                                     live_tokens, chunks):
    """Host arithmetic on the fetched counts: a block in one call exact, a
    prompt in chunks a whole tile more for each further chunk that holds a
    real token, a padding chunk nothing; every sorted row where the program
    holds ``lax.ragged_dot``."""
    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX

    cfg = get_config("olmoe-1b-7b", num_layers=2, hidden_size=D,
                     intermediate_size=F, num_heads=4, vocab_size=256,
                     num_experts=E, moe_top_k=TOPK, max_seq_len=512,
                     dtype=jnp.bfloat16)
    counts = np.zeros((2, E), np.int64)
    counts[0, :3] = (live_tokens * TOPK - 7, 4, 3)      # every pair held
    counts[1, 5] = 77
    rows = 2048 * TOPK
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: None)
    assert T.expert_rows_moved(cfg, 1, bucket, counts, live_tokens) == (
        2 * bucket * TOPK,) * 2
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: True)
    assert T._moe_chunks(cfg, 1, bucket) == bucket // 2048
    total, moved = T.expert_rows_moved(cfg, 1, bucket, counts, live_tokens)
    tile = live_rows.ROWS_IN_TILE
    assert total == 2 * bucket * TOPK
    assert moved == sum(
        min(chunks * rows, -(-n // tile) * tile + (chunks - 1) * tile)
        for n in (live_tokens * TOPK, 77))
    assert live_tokens * TOPK + 77 <= moved <= chunks * rows * 2
    assert T.expert_rows_moved(cfg, 1, bucket, counts * 0, 0) == (total, 0)
