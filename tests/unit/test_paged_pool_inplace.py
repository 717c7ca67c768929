"""The paged forward keeps the KV page pool in place across its layer scan
(ISSUE 25).

(a) Equivalence, twice.  Against a plain Python loop over layers that
slices one layer's pool out of the stack, runs the paged block on that
private slice and stacks the slices back — what scanning the pool as
``xs``/``ys`` computed — logits and every pool leaf must be ``array_equal``.
Against a row-granular block written here (every token's row scattered at
``page*page_size + offset`` with masked tokens sent to the trash row, every
slot's rows gathered one by one, attention over ``T`` flat rows — the
semantics the whole-page merge and gather replaced): every real page
``array_equal``, logits to float32 rounding.  Shapes: the decode tick with
an inactive slot, a padded tail prefill from an unaligned start, a verify-k
block straddling the page table's end; pools: bf16 and int8 with scale
planes; cases with adapters.

(b) Structure: the layer scan's carry holds every pool leaf, nothing it
scans over or stacks has the pool's page axis, and its body holds no
``dynamic_slice``/``dynamic_update_slice`` of the pool and no gather or
scatter that cuts a page — the ops that, one layer's slice or one row at a
time, made the TPU compiler copy the pool (PERF.md, PR 25).

(c) The read of live pages only (ISSUE 27, ISSUE 29): the read walks a flat
list of the call's live (slot, page) pairs, every slot to its own length,
two pairs a slot of the program a step.  Against the same row-granular
block, which attends over every row of every slot's page-table row: lengths
one short of, at and one past a page's and a pair of pages' end, a slot at
``max_model_len - 1`` beside short ones, an inactive slot (last, and between
two live ones), no active slot at all, a tail prefill over shared pages and
a verify-k block across a page pair's end, on a table of six pages.  The
softmax is computed blockwise, so what a layer writes after the first may
differ from the reference in the last place; the first layer's pages are
``array_equal``.  No gather in the layer body is as wide as the page-table
row, and the device's order of the pool's axes (``pool_order``) changes the
view the read gathers from, never a number.

(d) Against the contiguous cache (ISSUE 29): slots of very unequal length in
one decode call — one at the table's last row, one exactly at a page's edge,
one of three rows, an idle one between two live ones — give the logits and
the K/V rows that ``forward_cached`` gives each request alone, and the
logits of the plain ``forward``; with grouped heads, alibi, learned
positions and an int8 pool.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import get_config, init_params
from deepspeed_tpu.models.transformer import (_adapter_proj, _attend_paged,
                                              _attn_out, _block,
                                              _lm_head, _mlp, _norm,
                                              _paged_read_plan,
                                              _paged_write_plan, _qkv,
                                              _adapter_delta, _sm_scale,
                                              forward_paged,
                                              init_paged_cache, kv_dequantize,
                                              kv_quantize_rows,
                                              paged_read_pairs)

L, NUM_PAGES, PAGE, B = 3, 9, 8, 3   # 4 pages a slot: max_model_len 32
CFG = get_config("tiny-gqa", num_layers=L, dtype=jnp.float32)
# slot -> physical pages; 0 = unallocated (the trash page)
PAGE_TABLE = np.array([[3, 1, 7, 5], [2, 8, 4, 0], [6, 0, 0, 0]], np.int32)
# the bounded read's table: 6 pages a slot (max_model_len 48) = three steps
# of two pages, 15 physical pages
WIDE_PAGES = 15
WIDE_TABLE = np.array([[3, 1, 7, 5, 11, 13], [2, 8, 4, 0, 0, 0],
                       [6, 9, 0, 0, 0, 0]], np.int32)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(11))


def _filled_pool(kv_dtype, seed=5, num_pages=NUM_PAGES):
    """A pool holding random rows everywhere, so a row written to or read
    from the wrong layer or page changes the result."""
    cache = init_paged_cache(CFG, num_pages, PAGE, dtype=jnp.bfloat16,
                             kv_dtype=kv_dtype)
    rng = np.random.default_rng(seed)
    out = {}
    for k, a in cache.items():
        if a.dtype == jnp.int8:
            out[k] = jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        elif k.endswith("_scale"):
            out[k] = jnp.asarray(rng.uniform(0.001, 0.02, a.shape),
                                 jnp.float32)
        else:
            out[k] = jnp.asarray(rng.normal(size=a.shape), a.dtype)
    return out


def _shape_case(name):
    """tokens, page_table, start, seq_mask of one program shape."""
    rng = np.random.default_rng(17)
    if name == "decode":          # [B,1], slot 2 inactive
        tokens = rng.integers(1, 250, (B, 1))
        start = [5, 17, 0]
        mask = np.array([[True], [True], [False]])
        table = PAGE_TABLE
    elif name == "prefill":       # [1,S_pad] tail prefill from inside a page
        tokens = rng.integers(1, 250, (1, 16))
        start = [11]
        mask = (np.arange(16) < 9)[None, :]
        table = PAGE_TABLE[:1]
    elif name == "verify":        # verify-k [B,k+1]; slot 0 runs past row 31
        tokens = rng.integers(1, 250, (B, 4))
        start = [30, 12, 3]
        mask = np.ones((B, 4), bool)
        table = PAGE_TABLE
    elif name.startswith("len"):  # decode, slot 0 holds n rows; slot 2 idle
        tokens = rng.integers(1, 250, (B, 1))
        start = [int(name[3:]), 3, 0]
        mask = np.array([[True], [True], [False]])
        table = WIDE_TABLE
    elif name == "long-beside-short":   # slot 0 writes the table's last row
        tokens = rng.integers(1, 250, (B, 1))
        start = [47, 2, 9]
        mask = np.ones((B, 1), bool)
        table = WIDE_TABLE
    elif name == "idle-between":        # slot 1 idle between two live ones
        tokens = rng.integers(1, 250, (B, 1))
        start = [37, 5, 12]
        mask = np.array([[True], [False], [True]])
        table = WIDE_TABLE
    elif name == "all-inactive":
        tokens = rng.integers(1, 250, (B, 1))
        start = [21, 3, 0]
        mask = np.zeros((B, 1), bool)
        table = WIDE_TABLE
    elif name == "tail-prefill":  # 9 real tokens behind 19 shared rows
        tokens = rng.integers(1, 250, (1, 16))
        start = [19]
        mask = (np.arange(16) < 9)[None, :]
        table = WIDE_TABLE[:1]
    else:                         # verify-k across rows 15|16: a step's end
        assert name == "verify-straddle", name
        tokens = rng.integers(1, 250, (B, 4))
        start = [14, 6, 3]
        mask = np.ones((B, 4), bool)
        table = WIDE_TABLE
    return (jnp.asarray(tokens, jnp.int32), jnp.asarray(table),
            jnp.asarray(start, jnp.int32), jnp.asarray(mask))


def _adapters(seed=23, rank=4, slots=B):
    rng = np.random.default_rng(seed)
    d, hq = CFG.hidden_size, CFG.num_heads * CFG.dims_per_head
    dims = {"wq": (d, hq), "wo": (hq, d)}
    return {"scale": jnp.asarray([0.5, 0.0, 2.0][:slots], jnp.float32),
            "factors": {
                t: {"A": jnp.asarray(
                        rng.normal(size=(L, slots, di, rank)) * 0.1,
                        jnp.float32),
                    "B": jnp.asarray(
                        rng.normal(size=(L, slots, rank, do)) * 0.1,
                        jnp.float32)}
                for t, (di, do) in dims.items()}}


def _forward_layers(block, params, tokens, cache, adapters):
    """embed -> ``block(lp, x, one layer's pool leaves, its factors)`` for
    each layer in a Python loop -> head; the slices stacked back."""
    x = params["embed"].astype(CFG.dtype)[tokens]
    new = {k: [] for k in cache}
    for layer in range(CFG.num_layers):
        lp = jax.tree_util.tree_map(lambda a: a[layer], params["layers"])
        ad = (jax.tree_util.tree_map(lambda a: a[layer], adapters["factors"])
              if adapters is not None else None)
        x, out = block(lp, x, {k: a[layer] for k, a in cache.items()}, ad)
        for k in cache:
            new[k].append(out[k])
    x = _norm(CFG, x, params["final_norm_scale"],
              params.get("final_norm_bias"))
    return _lm_head(CFG, params, x), {k: jnp.stack(v) for k, v in new.items()}


def _forward_sliced(params, tokens, cache, page_table, start, seq_mask,
                    adapters=None):
    """The pool handled one layer's slice at a time: what ``forward_paged``
    computed with the pool as scan xs/ys."""
    positions = start[:, None] + jnp.arange(tokens.shape[1], dtype=jnp.int32)
    write = _paged_write_plan(page_table, start, seq_mask,
                              cache["k"].shape[2])
    read = _paged_read_plan(page_table, start, seq_mask,
                            cache["k"].shape[2])

    def block(lp, x, pools, ad):
        out = _block(
            CFG, lp, x, positions, jax.random.PRNGKey(0),
            _attend_paged(CFG, pools, write, read),
            proj=_adapter_proj(
                ad, None if adapters is None else adapters["scale"]))
        return out[0], out[3]

    return _forward_layers(block, params, tokens, cache, adapters)


def _forward_rows(params, tokens, cache, page_table, start, seq_mask,
                  adapters=None):
    """Row-granular paging: one scatter index per token (masked tokens and
    positions past the table go to row 0 of the trash page), one gather
    index per row of each slot, attention over all T flat rows of the
    page-table row."""
    cfg = CFG
    Bq, S = tokens.shape
    ps, maxp = cache["k"].shape[2], page_table.shape[1]
    hd, nkv, G = cfg.dims_per_head, cfg.kv_heads, cfg.num_heads // cfg.kv_heads
    positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    raw = positions // ps
    phys = jnp.take_along_axis(page_table, jnp.minimum(raw, maxp - 1), axis=1)
    write_idx = jnp.where(seq_mask & (raw < maxp),
                          phys * ps + positions % ps, 0).reshape(Bq * S)
    gather_idx = (page_table[:, :, None] * ps
                  + jnp.arange(ps, dtype=jnp.int32)).reshape(Bq, maxp * ps)
    t = jnp.arange(maxp * ps, dtype=jnp.int32)

    def block(lp, x, pools, ad):
        def proj(y, name, hin):
            if ad is not None and name in ad:
                y = y + _adapter_delta(hin, ad[name],
                                       adapters["scale"]).astype(y.dtype)
            return y

        flat = {k: a.reshape(-1, *a.shape[2:]) for k, a in pools.items()}
        h = _norm(cfg, x, lp["attn_norm_scale"], lp.get("attn_norm_bias"))
        q, k, v = _qkv(cfg, lp, h, positions, proj)
        k, v = (a.reshape(Bq * S, nkv, hd) for a in (k, v))
        if "k_scale" in flat:
            (k, ks), (v, vs) = kv_quantize_rows(k), kv_quantize_rows(v)
            flat["k_scale"] = flat["k_scale"].at[write_idx].set(ks)
            flat["v_scale"] = flat["v_scale"].at[write_idx].set(vs)
        flat["k"] = flat["k"].at[write_idx].set(k.astype(flat["k"].dtype))
        flat["v"] = flat["v"].at[write_idx].set(v.astype(flat["v"].dtype))
        ck, cv = flat["k"][gather_idx], flat["v"][gather_idx]
        if "k_scale" in flat:
            ck = kv_dequantize(ck, flat["k_scale"][gather_idx], cfg.dtype)
            cv = kv_dequantize(cv, flat["v_scale"][gather_idx], cfg.dtype)
        scores = jnp.einsum("bskgd,btkd->bkgst",
                            q.reshape(Bq, S, nkv, G, hd), ck)
        scores = scores.astype(jnp.float32) * _sm_scale(cfg, hd)
        ok = t[None, None, :] <= positions[:, :, None]
        scores = jnp.where(ok[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        attn = jnp.einsum("bkgst,btkd->bskgd", probs, cv)
        x = x + _attn_out(cfg, lp, attn.reshape(Bq, S, -1, hd), proj)
        h = _norm(cfg, x, lp["mlp_norm_scale"], lp.get("mlp_norm_bias"))
        m = _mlp(cfg, lp, h, jax.random.PRNGKey(0), deterministic=True)[0]
        return x + m, {k: a.reshape(pools[k].shape) for k, a in flat.items()}

    return _forward_layers(block, params, tokens, cache, adapters)


CASES = [(shape, kv, False) for shape in ("decode", "prefill", "verify")
         for kv in (None, "int8")] + [("decode", None, True),
                                      ("verify", "int8", True)]


@pytest.mark.parametrize(
    "shape,kv_dtype,with_adapters", CASES,
    ids=[f"{s}-{kv or 'bf16'}{'-adapters' if ad else ''}"
         for s, kv, ad in CASES])
def test_forward_paged_equals_per_layer_slices(params, shape, kv_dtype,
                                               with_adapters):
    tokens, table, start, mask = _shape_case(shape)
    cache = _filled_pool(kv_dtype)
    adapters = _adapters() if with_adapters else None
    want_logits, want = jax.jit(_forward_sliced)(
        params, tokens, cache, table, start, mask, adapters)
    got_logits, got = jax.jit(functools.partial(forward_paged, CFG))(
        params, tokens, cache, table, start, mask, adapters)
    assert sorted(got) == sorted(cache)
    for k in cache:
        assert got[k].shape == cache[k].shape and got[k].dtype == cache[k].dtype
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=f"pool leaf {k}")
        # the forward did write: a pool handed back untouched proves nothing
        assert not np.array_equal(np.asarray(got[k]), np.asarray(cache[k]))
    real = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(got_logits)[real],
                                  np.asarray(want_logits)[real])


_PAGED = jax.jit(functools.partial(forward_paged, CFG),
                 static_argnames=("pool_order",))
_ROWS = jax.jit(_forward_rows)

# lengths one short of, at and one past the end of a page (8 rows) that is
# no step's end, of the first step (16) and of the second (32)
ROW_CASES = (
    [(shape, kv, False) for shape in ("decode", "prefill", "verify")
     for kv in (None, "int8")]
    + [(f"len{n}", None, False) for n in (7, 8, 9, 15, 16, 17, 31, 32, 33)]
    + [(f"len{n}", "int8", False) for n in (15, 16, 17)]
    + [("len16", None, True), ("len17", "int8", True)]
    + [(shape, kv, ad)
       for shape in ("long-beside-short", "idle-between", "all-inactive",
                     "tail-prefill", "verify-straddle")
       for kv, ad in ((None, False), ("int8", True))])


def _assert_pools_match(got, want, layers, exact):
    """Every real page (page 0 is the trash page: the row scatter dumps
    masked tokens there, the page merge writes them nowhere) of ``layers``:
    bit for bit, or to the last place of what a leaf stores."""
    for k in got:
        g = np.asarray(got[k])[layers, 1:]
        w = np.asarray(want[k])[layers, 1:]
        if exact:
            np.testing.assert_array_equal(g, w, err_msg=f"pool leaf {k}")
        elif g.dtype == np.int8:
            assert np.abs(g.astype(np.int32) - w).max() <= 1, k
            assert (g != w).mean() < 1e-3, k
        else:
            np.testing.assert_allclose(
                g.astype(np.float32), w.astype(np.float32),
                rtol=2.0 ** -7 if g.dtype == jnp.bfloat16 else 1e-5,
                atol=1e-6, err_msg=f"pool leaf {k}")


@pytest.mark.parametrize(
    "shape,kv_dtype,with_adapters", ROW_CASES,
    ids=[f"{s}-{kv or 'bf16'}{'-adapters' if ad else ''}"
         for s, kv, ad in ROW_CASES])
def test_page_merge_equals_row_scatter_and_gather(params, shape, kv_dtype,
                                                  with_adapters):
    tokens, table, start, mask = _shape_case(shape)
    wide = table.shape[1] == WIDE_TABLE.shape[1]
    cache = _filled_pool(kv_dtype,
                         num_pages=WIDE_PAGES if wide else NUM_PAGES)
    adapters = (_adapters(slots=tokens.shape[0]) if with_adapters
                else None)
    want_logits, want = _ROWS(params, tokens, cache, table, start, mask,
                              adapters)
    got_logits, got = _PAGED(params, tokens, cache, table, start, mask,
                             adapters)
    real = np.asarray(mask)
    if real.any():
        assert not np.array_equal(np.asarray(got["k"]),
                                  np.asarray(cache["k"]))
    else:   # nothing real: every real page is as it was, nothing is NaN
        _assert_pools_match(got, cache, slice(None), exact=True)
        assert np.isfinite(np.asarray(got_logits)).all()
    # the first layer writes before any attention has run
    _assert_pools_match(got, want, slice(0, 1), exact=True)
    _assert_pools_match(got, want, slice(None), exact=False)
    np.testing.assert_allclose(np.asarray(got_logits)[real],
                               np.asarray(want_logits)[real],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("order", [(0, 1, 3, 4, 2), (0, 1, 4, 2, 3),
                                   (1, 0, 2, 3, 4)],
                         ids=["page-minor", "heads-minor", "not-stackable"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_pool_order_changes_the_view_not_the_numbers(params, kv_dtype, order):
    """``pool_order`` says how the device stores a leaf; the read gathers
    from the view in that order (or, where the page axes do not lead, from
    the leaf as it is) and computes what it computes without it."""
    tokens, table, start, mask = _shape_case("verify-straddle")
    cache = _filled_pool(kv_dtype, num_pages=WIDE_PAGES)
    want_logits, want = _PAGED(params, tokens, cache, table, start, mask)
    got_logits, got = _PAGED(params, tokens, cache, table, start, mask,
                             pool_order=order)
    _assert_pools_match(got, want, slice(0, 1), exact=True)
    _assert_pools_match(got, want, slice(None), exact=False)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits), rtol=1e-5, atol=1e-5)


UNEQUAL = {   # overrides of "tiny" (rotary, RMSNorm, 4 heads), pool dtype
    "gqa": (dict(num_heads=8, num_kv_heads=2), None),
    "alibi": (dict(position="alibi", norm="layernorm", activation="gelu"),
              None),
    "learned-int8": (dict(position="learned", norm="layernorm",
                          attn_bias=True, mlp_bias=True), "int8"),
}


@pytest.mark.parametrize("topology", list(UNEQUAL))
def test_unequal_slots_equal_the_contiguous_cache(topology):
    """One decode call over five slots of 32, 0 (idle), 8, 30 and 3 rows
    after it (page 8, four pages a slot: the table's last row, a page's
    edge, an idle slot between live ones), each prefilled through the paged
    forward alone.  Every live slot's logits and every K/V row it holds are
    what ``forward_cached`` gives that request alone, and its logits what
    the plain forward gives."""
    from deepspeed_tpu.models import forward, forward_cached, init_cache

    overrides, kv_dtype = UNEQUAL[topology]
    cfg = get_config("tiny", dtype=jnp.float32, **overrides)
    params = init_params(cfg, jax.random.PRNGKey(7))
    held = [32, 0, 8, 30, 3]          # rows after the decode call
    Bq, maxp = len(held), 4
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 250, (n,)).astype(np.int32) for n in held]
    table = np.zeros((Bq, maxp), np.int32)
    table[[0, 2, 3, 4]] = 1 + rng.permutation(4 * maxp).reshape(4, maxp)
    cache = init_paged_cache(cfg, 1 + 4 * maxp, PAGE, dtype=jnp.float32,
                             kv_dtype=kv_dtype)
    paged = jax.jit(functools.partial(forward_paged, cfg))
    for b, toks in enumerate(prompts):
        if len(toks) > 1:             # all but the last token, bucket 32
            pad = np.zeros((1, 32), np.int32)
            pad[0, :len(toks) - 1] = toks[:-1]
            _, cache = paged(params, jnp.asarray(pad), cache,
                             jnp.asarray(table[b:b + 1]),
                             jnp.zeros((1,), jnp.int32),
                             jnp.asarray(np.arange(32) < len(toks) - 1)[None])
    live = np.array([n > 0 for n in held])
    last = np.array([[t[-1] if len(t) else 0] for t in prompts], np.int32)
    logits, cache = paged(
        params, jnp.asarray(last), cache, jnp.asarray(table),
        jnp.asarray([max(n - 1, 0) for n in held], jnp.int32),
        jnp.asarray(live[:, None]))
    assert np.isfinite(np.asarray(logits)).all()

    tol = dict(rtol=2e-2, atol=2e-2) if kv_dtype else dict(rtol=2e-5,
                                                            atol=2e-5)
    for b, toks in enumerate(prompts):
        if not len(toks):
            continue
        n = len(toks)
        pos = jnp.arange(n, dtype=jnp.int32)[None]
        want = forward(cfg, params, jnp.asarray(toks)[None], attn_impl="xla")
        np.testing.assert_allclose(np.asarray(logits[b, 0]),
                                   np.asarray(want[0, -1]), **tol)
        cached, cc = forward_cached(
            cfg, params, jnp.asarray(toks)[None],
            init_cache(cfg, 1, n, dtype=jnp.float32), pos,
            jnp.ones((1, n), bool))
        np.testing.assert_allclose(np.asarray(logits[b, 0]),
                                   np.asarray(cached[0, -1]), **tol)
        # the rows the slot holds, through its page-table row
        t = np.arange(n)
        for name in ("k", "v"):
            got = np.asarray(cache[name])[:, table[b, t // PAGE], t % PAGE]
            if kv_dtype:
                got = got * np.asarray(cache[name + "_scale"])[
                    :, table[b, t // PAGE], t % PAGE][..., None, None]
            np.testing.assert_allclose(got, np.asarray(cc[name])[:, 0],
                                       **tol, err_msg=f"slot {b} {name}")


def _sub_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from _sub_jaxprs(inner)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_layer_scan_carries_the_pool_in_place(params, kv_dtype):
    tokens, table, start, mask = _shape_case("decode")
    cache = _filled_pool(kv_dtype)
    jaxpr = jax.make_jaxpr(functools.partial(forward_paged, CFG))(
        params, tokens, cache, table, start, mask, _adapters()).jaxpr
    scans = [e for j in _sub_jaxprs(jaxpr) for e in j.eqns
             if e.primitive.name == "scan" and e.params["length"] == L]
    assert len(scans) == 1, "one layer scan"
    scan = scans[0]
    n_const, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    carry = [v.aval for v in scan.invars[n_const:n_const + n_carry]]
    scanned = [v.aval for v in scan.invars[n_const + n_carry:]]
    assert not scan.outvars[n_carry:], "the scan stacks nothing"

    stacked = {k: (L * NUM_PAGES,) + a.shape[2:] for k, a in cache.items()}
    for k, a in cache.items():
        assert any(c.shape == stacked[k] and c.dtype == a.dtype
                   for c in carry), \
            f"pool leaf {k} {stacked[k]} is not in the scan's carry"
    for a in scanned:
        assert a.shape[1:3] != (NUM_PAGES, PAGE), \
            f"the scan slices something with the page axis: {a}"

    # inside the body the pool is only ever gathered from and scattered
    # into, whole pages at a time: no op cuts a layer or a row out of it
    pool_shapes = set(stacked.values())
    for a in cache.values():
        pool_shapes |= {a.shape, a.shape[1:]}
    seen = set()
    for j in _sub_jaxprs(scan.params["jaxpr"].jaxpr):
        for e in j.eqns:
            shape = e.invars[0].aval.shape if e.invars else None
            if shape not in pool_shapes:
                continue
            name = e.primitive.name
            seen.add(name)
            assert name not in ("dynamic_slice", "dynamic_update_slice"), \
                f"{name} of the pool in the layer body: {e}"
            if name == "gather":
                assert tuple(e.params["slice_sizes"][1:]) == shape[1:], e
                # ... and never a slot's whole page-table row: the read
                # takes a step's pairs, the merge the pages it writes
                n_pages = int(np.prod(e.invars[1].aval.shape[:-1]))
                assert n_pages <= paged_read_pairs(*table.shape), e
                assert n_pages < table.size, e
            if name == "scatter":
                dn = e.params["dimension_numbers"]
                assert len(dn.update_window_dims) == len(shape) - 1, e
    assert {"scatter", "gather"} <= seen

    # the read's loop: one gather a pool leaf a step, of a step's pairs
    loops = [e for j in _sub_jaxprs(scan.params["jaxpr"].jaxpr)
             for e in j.eqns if e.primitive.name == "while"]
    assert len(loops) == 1, "one read loop in the layer body"
    reads = [e for j in _sub_jaxprs(loops[0].params["body_jaxpr"].jaxpr)
             for e in j.eqns if e.primitive.name == "gather"
             and e.invars[0].aval.shape in pool_shapes]
    assert len(reads) == len(cache)
    for e in reads:
        assert e.invars[1].aval.shape[0] == paged_read_pairs(*table.shape)
