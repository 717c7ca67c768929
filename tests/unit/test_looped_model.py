"""A looped model (Ouro, ``ouro``: ONE stack of layers run ``loop_passes``
times a token, four norms a layer, the final norm after every pass, keys and
values of its own for every (pass, layer)) through the model and the serving
engine: ``forward``, the contiguous cache and the paged path against the
plain reference for 1, 2 and 4 passes, where a pass's rows lie in the pool,
an engine whose pool is under the full reservation (pages, not slots, bound
admission), the span attrs and health counters, and every mechanism of
``cache_layout.REFUSED`` on such a cache."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark.lib import reference_ouro as R
from deepspeed_tpu.inference.cache_layout import REFUSED, CacheLayout
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models import transformer as T

LAYERS, PAGE = 3, 8
SERVE_KW = dict(b_slots=4, page_size=PAGE, max_model_len=64)


def tiny(**over):
    kw = dict(num_layers=LAYERS, hidden_size=64, intermediate_size=96,
              num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=256,
              max_seq_len=512, dtype=jnp.float32)
    kw.update(over)
    return get_config("ouro-2.6b", **kw)


def _params(cfg, seed=1):
    """Seeded weights with every norm scale away from 1, so that a norm left
    out, or one layer's scale taken for another's, shows."""
    p = init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 16))
    p["layers"] = {k: (v * (1 + 0.2 * jax.random.normal(next(keys), v.shape))
                       if k.endswith("norm_scale") else v)
                   for k, v in p["layers"].items()}
    p["final_norm_scale"] = p["final_norm_scale"] + 0.2 * jax.random.normal(
        next(keys), p["final_norm_scale"].shape)
    return p


@pytest.fixture(scope="module")
def params():
    return _params(tiny())      # the same leaves whatever the passes


@pytest.fixture(scope="module")
def engine(params):
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    return deepspeed_tpu.init_inference(
        model=CausalLM(tiny()), params=params, dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, (1, n)),
                       jnp.int32)


class F32Cache(CausalLM):       # the harness asks for a bfloat16 pool
    def init_paged_cache(self, *a, dtype=None, **kw):
        return super().init_paged_cache(*a, dtype=jnp.float32, **kw)


@functools.partial(jax.jit, static_argnums=0)
def _forward(cfg, params, toks):
    return T.forward(cfg, params, toks)


def _is_greedy(cfg, params, prompt, out) -> bool:
    seq = jnp.asarray(list(prompt) + list(out), jnp.int32)[None]
    logits = _forward(cfg, params, seq)[0, len(prompt) - 1:-1]
    return list(np.asarray(jnp.argmax(logits, -1))) == list(out)


def _requests(n, seed=0, lo=4, hi=20, new=(4, 12), **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", arrival_time=0.0,
                    max_new_tokens=int(rng.integers(*new)),
                    input_ids=rng.integers(0, 256, (int(rng.integers(lo, hi)),)
                                           ).astype(np.int32), **kw)
            for i in range(n)]


# ------------------------------------------------------------ the model

def test_the_named_base_is_the_published_model_and_counts_its_parameters():
    cfg = get_config("ouro-2.6b")
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size, cfg.num_heads,
            cfg.kv_heads, cfg.dims_per_head, cfg.intermediate_size,
            cfg.max_seq_len, cfg.loop_passes, cfg.sandwich_norm) == (
        2048, 48, 49152, 16, 16, 128, 5632, 65536, 4, True)
    assert (cfg.norm_eps, cfg.rope_theta, cfg.tie_embeddings) == (
        1e-6, 1e6, False)
    one = (get_config(cfg, num_layers=1).param_count
           - get_config(cfg, num_layers=0).param_count)
    assert one == 51_388_416
    assert cfg.param_count == 2_667_972_608
    assert T.cache_depth(cfg) == 192
    # 4 passes x 48 layers x K and V x 16 heads x 128 x bfloat16
    assert T.cache_depth(cfg) * 2 * 16 * 128 * 2 == 1_572_864
    t = tiny()
    leaves = jax.eval_shape(lambda: init_params(t, jax.random.PRNGKey(0)))
    assert t.param_count == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(leaves))
    # the weights' stack is num_layers deep whatever the passes
    assert all(v.shape[0] == LAYERS for v in leaves["layers"].values())
    assert {"attn_post_norm_scale", "mlp_post_norm_scale"} <= set(
        leaves["layers"])
    # the norms after the branches start at the residual scaling a norm
    # behind w_o and w_down would erase, the others at 1
    made = init_params(t, jax.random.PRNGKey(0))["layers"]
    np.testing.assert_allclose(made["mlp_post_norm_scale"],
                               1 / np.sqrt(2 * LAYERS))
    np.testing.assert_allclose(made["attn_post_norm_scale"],
                               1 / np.sqrt(2 * LAYERS))
    np.testing.assert_allclose(made["attn_norm_scale"], 1.0)
    assert set(T.param_specs(t)["layers"]) == set(leaves["layers"])


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_forward_is_the_reference(params, passes):
    cfg, toks = tiny(loop_passes=passes), _tokens(29)
    want = R.reference_logits(cfg, params, toks[0])
    assert R.rel_err(T.forward(cfg, params, toks)[0], want) < 1e-5
    # and a pass more or fewer is another model
    other = R.reference_logits(cfg, params, toks[0], passes=passes + 1)
    assert R.rel_err(T.forward(cfg, params, toks)[0], other) > 0.05


def test_one_pass_with_plain_wiring_is_todays_program():
    """``loop_passes`` 1 and no norm after the branches: the same program,
    equation for equation, as the model that has neither field set."""
    plain = get_config("tiny", dtype=jnp.float32)
    looped = get_config(
        "ouro-2.6b", num_layers=2, hidden_size=64, intermediate_size=128,
        num_heads=4, num_kv_heads=None, head_dim=None, vocab_size=256,
        max_seq_len=128, norm_eps=1e-5, rope_theta=10000.0, loop_passes=1,
        sandwich_norm=False, dtype=jnp.float32)
    assert looped == plain
    p = init_params(plain, jax.random.PRNGKey(0))
    toks = _tokens(12)
    cache = T.init_paged_cache(plain, 4, PAGE, dtype=jnp.float32)
    table = jnp.arange(1, 4, dtype=jnp.int32)[None]

    def paged(cfg):
        return str(jax.make_jaxpr(lambda p, c: T.forward_paged(
            cfg, p, toks, c, table, jnp.zeros((1,), jnp.int32),
            jnp.ones((1, 12), bool)))(p, cache))

    assert str(jax.make_jaxpr(lambda p: T.forward(looped, p, toks))(p)) == \
        str(jax.make_jaxpr(lambda p: T.forward(plain, p, toks))(p))
    assert paged(looped) == paged(plain)
    # a second pass is one more scan AROUND the layer scan, not a copy of it
    two = str(jax.make_jaxpr(lambda p, c: T.forward_paged(
        dataclasses.replace(plain, loop_passes=2), p, toks,
        T.init_paged_cache(dataclasses.replace(plain, loop_passes=2), 4,
                           PAGE, dtype=jnp.float32), table,
        jnp.zeros((1,), jnp.int32), jnp.ones((1, 12), bool)))(p, cache))
    assert two.count("scan[") == paged(plain).count("scan[") + 1


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_paged_prefill_then_decode_is_the_reference(params, passes):
    """The benchmark's own call: a padded prompt at start 0 and then
    teacher-forced single tokens, logits against the full forward."""
    from benchmark.traffic_kinds.serve_backlog import parity_paged

    err = parity_paged(R, F32Cache(tiny(loop_passes=passes)), params, 16,
                       21, 8, seed=5)
    assert max(err.values()) < 1e-5, err


@pytest.mark.parametrize("passes", [2, 4])
def test_the_contiguous_cache_is_the_reference(params, passes):
    cfg, toks, n = tiny(loop_passes=passes), _tokens(24, seed=2), 17
    want = R.reference_logits(cfg, params, toks[0])
    cache = T.init_cache(cfg, 1, 32, dtype=jnp.float32)
    assert cache["k"].shape[0] == passes * LAYERS
    logits, cache = T.forward_cached(cfg, params, toks[:, :n], cache,
                                     jnp.arange(n)[None],
                                     jnp.ones((1, n), bool))
    assert R.rel_err(logits[0], want[:n]) < 1e-5
    for i in range(n, 24):
        logits, cache = T.forward_cached(
            cfg, params, toks[:, i:i + 1], cache, jnp.full((1, 1), i),
            jnp.ones((1, 1), bool))
        assert R.rel_err(logits[0, 0], want[i]) < 1e-5


def _prefilled(cfg, params, toks, n, pages=4):
    model = F32Cache(cfg)
    cache = model.init_paged_cache(1 + pages, PAGE)
    table = jnp.arange(1, 1 + pages, dtype=jnp.int32)[None]
    prompt = jnp.zeros((1, pages * PAGE), jnp.int32).at[:, :n].set(
        toks[:, :n])
    _, cache = model.apply_paged(
        params, prompt, cache, table, jnp.zeros((1,), jnp.int32),
        (jnp.arange(pages * PAGE) < n)[None])
    return model, cache, table


def test_pass_r_layer_l_lies_at_r_times_layers_plus_l(params):
    """The pool's layer axis is ``passes x layers`` deep, pass-major: the
    rows of passes 0 .. r-1 are what the model of ``r`` passes writes (a
    pass depends on nothing after it), every (pass, layer) has rows of its
    own, and the trash page and unallocated pages stay empty."""
    cfg, toks, n = tiny(), _tokens(30, seed=4), 21
    _, cache, _ = _prefilled(cfg, params, toks, n)
    assert cache["k"].shape == (4 * LAYERS, 5, PAGE, 4, 16)
    for r in (1, 2, 3):
        _, part, _ = _prefilled(tiny(loop_passes=r), params, toks, n)
        assert part["k"].shape[0] == r * LAYERS
        for leaf in ("k", "v"):
            np.testing.assert_allclose(cache[leaf][:r * LAYERS], part[leaf],
                                       atol=1e-5)
    k = np.asarray(cache["k"])
    rows = k[:, 1:4].reshape(4 * LAYERS, 3 * PAGE, -1)[:, :n]
    for a in range(4 * LAYERS):
        assert np.abs(rows[a]).max() > 0
        for b in range(a):
            assert np.abs(rows[a] - rows[b]).max() > 1e-3, (a, b)
    assert not k[:, 0].any() and not k[:, 4].any()


@pytest.mark.parametrize("swap", [(0, 1), (2, 3), (0, 3)])
def test_a_pass_reading_another_passs_rows_fails(params, swap):
    cfg, toks, n = tiny(), _tokens(30, seed=4), 21
    model, cache, table = _prefilled(cfg, params, toks, n)
    want = R.reference_logits(cfg, params, toks[0, :n + 1])[n]

    def decode(cache):
        return model.apply_paged(params, toks[:, n:n + 1], cache, table,
                                 jnp.full((1,), n, jnp.int32),
                                 jnp.ones((1, 1), bool))[0][0, 0]

    assert R.rel_err(decode(cache), want) < 1e-5

    def swapped(cache):
        a, b = (slice(r * LAYERS, (r + 1) * LAYERS) for r in swap)
        return {leaf: v.at[a].set(v[b]).at[b].set(v[a])
                for leaf, v in cache.items()}

    assert R.rel_err(decode(swapped(cache)), want) > 0.01
    # and the layer checks name the first pass that read another's rows
    bad = R.layer_checks(cfg, params, 3, n_prompt=20, block_tokens=32,
                         pass_prompt=21, pass_bucket=32, n_decode=4,
                         page_size=16, tamper=swapped)
    failed = [k for k, c in bad.items() if c["rel_err"] > 1e-3]
    assert failed and failed[0] == f"pass_{swap[0] + 1}_x", bad


@pytest.mark.parametrize("what,mutate", [
    ("a pass fewer", {"passes": 3}),
    ("no norm between two passes", {"norm_every_pass": False}),
    ("no norm after the branches", {"post_norms": False}),
])
def test_a_mutated_reference_is_told_from_the_system(params, what, mutate):
    cfg = tiny()
    kw = dict(n_prompt=20, block_tokens=32, pass_prompt=21, pass_bucket=32,
              n_decode=4, page_size=16)
    good = R.layer_checks(cfg, params, 3, **kw)
    assert set(good) == {"block_padded_prompt", "pass_1_x", "pass_2_x",
                         "pass_3_x", "pass_4_x"}
    assert all(c["rel_err"] < 1e-5 for c in good.values()), good
    bad = R.layer_checks(cfg, params, 3, mutate=mutate, **kw)
    assert any(c["rel_err"] > c["tol"] for c in bad.values()), (what, bad)
    if what == "a pass fewer":      # fails by name: the pass that is missing
        assert [k for k, c in bad.items() if c["rel_err"] > c["tol"]] == [
            "pass_4_x"]


def test_what_the_loop_is_not_built_from_is_refused():
    for over, named in (({"num_experts": 4}, "expert layers"),
                        ({"pipeline_stages": 3}, "pipeline_stages"),
                        ({"scan_layers": False}, "scan_layers=False"),
                        ({"final_norm": False}, "final_norm")):
        with pytest.raises(NotImplementedError, match=named):
            init_params(tiny(**over), jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="sandwich_norm"):
        init_params(tiny(norm="layernorm"), jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="parallel_residual"):
        init_params(tiny(parallel_residual=True), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="loop_passes"):
        init_params(tiny(loop_passes=0), jax.random.PRNGKey(0))


def test_training_sums_the_gradient_over_the_passes(params):
    """The same weights in every pass: the gradient of a loss through
    ``forward`` is the reference's, summed over its four uses."""
    cfg, toks = tiny(), _tokens(12, seed=6)

    def loss(p):
        return T.cross_entropy_loss(T.forward(cfg, p, toks[:, :-1]),
                                    toks[:, 1:])

    def ref_loss(p):
        s = R.spec(cfg)
        x = p["embed"][toks[0, :-1]]
        pos = jnp.arange(11)
        for _ in range(4):
            for i in range(LAYERS):
                x = R.block(s, {k: v[i] for k, v in p["layers"].items()},
                            x, pos)
            x = R._rmsnorm(x, p["final_norm_scale"], s["eps"])
        return T.cross_entropy_loss((x @ p["lm_head"])[None], toks[:, 1:])

    got, want = jax.grad(loss)(params), jax.grad(ref_loss)(params)
    for leaf in ("wq", "w_down", "attn_post_norm_scale"):
        np.testing.assert_allclose(got["layers"][leaf], want["layers"][leaf],
                                   rtol=2e-3, atol=1e-6)


# ------------------------------------------------------- the serving engine

def test_the_cache_is_sized_by_its_depth(engine):
    cfg = engine.model.config
    lay = CacheLayout(cfg, 4, PAGE, 8, 33)
    assert (lay.kind, lay.passes, lay.depth) == ("looped", 4, 4 * LAYERS)
    assert "loop_passes" in lay.description
    sv = engine.serving(**SERVE_KW)
    assert sv._exec._pool_keys == ("k", "v")
    assert sv._exec.pools[0].shape == (4 * LAYERS, 33, PAGE, 4, 16)
    h = sv.health()
    per_token = 4 * LAYERS * 2 * 4 * 16 * 4        # float32 here
    assert h["kv_bytes_per_token"] == per_token
    assert h["kv_pool_bytes_total"] == per_token * 33 * PAGE
    assert h["loop_passes"] == 4 == sv._exec.mesh_info()["loop_passes"]
    assert h["admission_page_waits_total"] == 0
    # any other model says 1, and its own bytes a token
    plain = deepspeed_tpu.init_inference(
        model=CausalLM("tiny", dtype=jnp.float32), dtype="fp32",
        params=init_params(get_config("tiny"), jax.random.PRNGKey(0)))
    h = plain.serving(**SERVE_KW).health()
    assert (h["loop_passes"], h["kv_bytes_per_token"]) == (
        1, 2 * 2 * 4 * 16 * 4)


def test_a_pool_under_the_full_reservation_admits_head_of_line(engine):
    """Twelve requests of 2-4 pages through four slots over nine pages: the
    head of the queue waits for pages with slots free, nothing overtakes it,
    a slot takes its pages as it grows and the youngest gives them up when
    the pool has none, every request finishes with what greedy ``forward``
    yields, and the pool ends balanced.  One decode program; a program a
    prefill bucket."""
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)

    cfg = engine.model.config
    reqs = _requests(12, seed=3, lo=6, hi=20, new=(6, 14))
    sv = engine.serving(num_pages=10, **SERVE_KW)
    assert sv.num_pages == 10 < 1 + 4 * 8
    get_tracer().reset()    # another test's spans are not this run's
    configure_tracer(enabled=True)
    try:
        results = {r.rid: r for r in sv.run(reqs)}
        spans = [s for s in get_tracer().recorder.snapshot()
                 if isinstance(s, Span)]
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    for q in reqs:
        out = results[q.rid].output_ids
        assert len(out) == q.max_new_tokens
        assert _is_greedy(cfg, engine.params, q.input_ids, out), q.rid
    order = sorted(results.values(),
                   key=lambda r: (r.admit_s, r.first_token_s))
    assert [r.rid for r in order] == [q.rid for q in reqs]      # FIFO
    acct = sv.page_accounting()
    # nothing is held but what the prefix index keeps
    assert acct["balanced"] and acct["referenced"] == acct["cached"]
    h = sv.health()
    assert h["admission_page_waits_total"] > 0
    assert h["pages_hwm"] <= 9 and h["lookahead_dropped_total"] == 0
    inv = sv.program_inventory()
    assert inv["decode"] == 1 and sv._exec._decode_prog._cache_size() == 1
    assert set(inv["prefill_buckets"]) <= {8, 16, 32}
    ticks = [s.attrs for s in spans if s.name == "serve.tick"
             and s.attrs and "page_wait" in s.attrs]
    decode = [s.attrs for s in spans if s.name == "serve.decode"
              and s.attrs and "kv_bytes" in s.attrs]
    prefill = [s.attrs for s in spans if s.name == "serve.prefill"]
    # a prefill an admission, and one or more a readmission
    assert h["preemptions_total"] >= 1
    assert sum(r.preemptions for r in results.values()) == h[
        "preemptions_total"]
    assert ticks and decode and len(prefill) >= 12 + h["preemptions_total"]
    assert len([s for s in spans if s.name == "serve.admit"]) == 12 + h[
        "preemptions_total"]
    waited = [a for a in ticks if a["page_wait"]]
    assert waited and len(waited) < len(ticks)
    # a tick that waited for pages had a slot free and too few pages for
    # the head: under 4 slots decoding, under 4 pages free
    assert all(len(a["slot_rids"]) < 4 and a["pages_free"] < 4
               for a in waited)
    per_token = h["kv_bytes_per_token"]
    for a in decode:
        assert a["passes"] == 4
        # the rows the tick's slots hold, the row each writes counted in
        rows, rest = divmod(a["kv_bytes"], per_token)
        assert rest == 0 and a["own_slots"] <= rows - a["live_rows"] <= 4
        assert a["live_rows"] <= a["gathered_rows"]
    for a in prefill:
        assert a["passes"] == 4
        assert a["kv_bytes"] == a["tokens"] * per_token


def test_the_full_reservation_never_waits_for_a_page(engine):
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)

    sv = engine.serving(**SERVE_KW)
    get_tracer().reset()    # another test's spans are not this run's
    configure_tracer(enabled=True)
    try:
        sv.run(_requests(9, seed=8))
        waits = [s.attrs["page_wait"] for s in
                 get_tracer().recorder.snapshot() if isinstance(s, Span)
                 and s.name == "serve.tick" and s.attrs
                 and "page_wait" in s.attrs]
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    assert waits and not any(waits)
    assert sv.health()["admission_page_waits_total"] == 0


# ------------------------------------- every mechanism of REFUSED, looped

@pytest.fixture(scope="module")
def plain_outputs(engine):
    """What the plain engine yields for the mechanisms' shared stream: four
    requests behind one 19-token prefix (two pages and a partial third)."""
    return {r.rid: list(r.output_ids)
            for r in engine.serving(prefix_cache=False, **SERVE_KW).run(
                _shared_prefix_stream())}


def _shared_prefix_stream():
    rng = np.random.default_rng(21)
    prefix = rng.integers(0, 256, (19,)).astype(np.int32)
    return [Request(rid=f"s{i}", arrival_time=0.0, max_new_tokens=6,
                    input_ids=np.concatenate(
                        [prefix, rng.integers(0, 256, (3 + i,)).astype(
                            np.int32)])) for i in range(4)]


def _outputs(sv, reqs=None):
    return {r.rid: list(r.output_ids)
            for r in sv.run(reqs or _shared_prefix_stream())}


def _prefix_sharing_and_cow(engine, plain):
    sv = engine.serving(prefix_cache=True, **SERVE_KW)
    assert _outputs(sv) == plain
    h = sv.health()
    assert h["prefix_hits_total"] > 0 and h["cow_copies_total"] > 0
    assert h["prefix_shared_tokens_total"] >= 16
    assert sv.page_accounting()["balanced"]


def _tiering(engine, plain):
    sv = engine.serving(num_pages=10, host_tier_pages=8, **SERVE_KW)
    assert _outputs(sv) == plain
    # pressure: distinct prompts push the cached prefix pages to the host,
    # and the shared stream brings them back
    sv.run(_requests(6, seed=31, lo=18, hi=24, new=(4, 6)))
    again = _shared_prefix_stream()
    for q in again:
        q.rid = "again-" + q.rid
    assert {k[6:]: v for k, v in _outputs(sv, again).items()} == plain
    h = sv.health()
    assert h["demotions_total"] > 0 and h["promotions_total"] > 0
    assert sv.page_accounting()["balanced"]
    # a page moved is every pass's rows of it
    slabs = sv._exec.extract(0)
    assert slabs[0].shape == (4 * LAYERS, PAGE, 4, 16)


def _int8_pool(engine, plain):
    from benchmark.traffic_kinds.serve_backlog import parity_paged

    class Int8Cache(CausalLM):
        def init_paged_cache(self, *a, dtype=None, **kw):
            return super().init_paged_cache(*a, dtype=jnp.float32,
                                            kv_dtype="int8", **kw)

    cfg = engine.model.config
    err = parity_paged(R, Int8Cache(cfg), engine.params, 16, 21, 8, seed=5)
    assert 1e-5 < max(err.values()) < 0.05, err       # int8 rows, still close
    sv = engine.serving(kv_dtype="int8", **SERVE_KW)
    assert sv._exec._pool_keys == ("k", "v", "k_scale", "v_scale")
    assert sv._exec.pools[2].shape == (4 * LAYERS, 33, PAGE)
    out = _outputs(sv)
    assert {k: len(v) for k, v in out.items()} == {
        k: len(v) for k, v in plain.items()}
    assert sv.page_accounting()["balanced"]


def _tensor_sharded(engine, plain):
    from deepspeed_tpu.parallel.mesh import initialize_serving_mesh

    mesh = initialize_serving_mesh(tp=2)
    sharded = deepspeed_tpu.init_inference(
        model=CausalLM(engine.model.config), params=jax.device_get(
            engine.params), dtype="fp32", mesh=mesh)
    sv = sharded.serving(**SERVE_KW)
    assert _outputs(sv) == plain
    h = sv.health()
    assert h["kv_pool_bytes_per_device"] * 2 == h["kv_pool_bytes_total"]


def _adapters(engine, plain):
    from deepspeed_tpu.inference.adapters import AdapterRegistry
    from deepspeed_tpu.runtime.lora import LoRAConfig

    cfg = LoRAConfig(rank=4, alpha=8.0)
    rng = np.random.default_rng(9)
    lora = {}
    for t in cfg.targets:
        L, d_in, d_out = np.shape(engine.params["layers"][t])
        assert L == LAYERS          # factors a WEIGHT layer, used every pass
        lora[t] = {"A": rng.standard_normal((L, d_in, 4)).astype(np.float32)
                   / 2, "B": rng.standard_normal((L, 4, d_out)).astype(
                       np.float32) * 0.05}
    reg = AdapterRegistry(engine.params["layers"])
    reg.register("acme", lora, cfg)
    sv = engine.serving(adapters=reg, **SERVE_KW)
    assert _outputs(sv) == plain            # base-model traffic: zero factors
    tenant = _shared_prefix_stream()
    for q in tenant:
        q.adapter_id = "acme"
    fused = deepspeed_tpu.init_inference(
        model=CausalLM(engine.model.config), dtype="fp32",
        params=reg.fuse(engine.params, "acme"))
    want = _outputs(fused.serving(**SERVE_KW))
    assert want != plain
    assert _outputs(sv, tenant) == want


def _speculative(engine, plain):
    from deepspeed_tpu.inference.speculative import (SpeculativeConfig,
                                                     layer_skip_draft)

    dm, dp = layer_skip_draft(engine.model, engine.params, 2)
    assert dm.config.loop_passes == 4       # a looped draft of two layers
    sv = engine.serving(speculative=SpeculativeConfig(
        draft_model=dm, draft_params=dp, k=3), **SERVE_KW)
    assert _outputs(sv) == plain
    assert sv.health()["spec_mean_accepted_len"] >= 1.0
    assert sv.page_accounting()["balanced"]


def _pages_follow_length(engine, plain):
    """A pool under the full reservation: a slot of a looped model grows a
    page (every pass deep) at a time, gives its pages up when the pool has
    none and is rebuilt by tail prefills through all four passes."""
    reqs = _requests(10, seed=31, new=(20, 40))
    want = _outputs(engine.serving(**SERVE_KW), reqs)
    sv = engine.serving(num_pages=9, **SERVE_KW)
    assert _outputs(sv, reqs) == want
    h = sv.health()
    assert h["preemptions_total"] >= 1 and h["page_grows_total"] > 0
    assert sv.page_accounting()["balanced"]


MECHANISMS = {
    "pages that follow a slot's length (recompute preemption)":
        _pages_follow_length,
    "tensor-sharded heads (tp > 1)": _tensor_sharded,
    "copy-on-write page snapshots (prefix_cache=True)":
        _prefix_sharing_and_cow,
    "KV-page tiering": _tiering,
    "the int8 pool": _int8_pool,
    "multi-tenant adapters": _adapters,
    "prefix sharing (prefix_cache=True)": _prefix_sharing_and_cow,
    "speculative decoding": _speculative,
}


def test_every_mechanism_is_tried():
    assert set(MECHANISMS) == set(REFUSED)


@pytest.mark.parametrize("mechanism", list(MECHANISMS))
def test_a_mechanism_works_on_a_looped_cache_or_refuses_it_by_name(
        engine, plain_outputs, mechanism):
    """Each mechanism of the one table either serves a looped model token
    for token, or the table lists ``looped`` and it refuses by both names."""
    lay = CacheLayout(engine.model.config, 4, PAGE, 8, 33)
    if lay.allows(mechanism):
        MECHANISMS[mechanism](engine, plain_outputs)
        return
    with pytest.raises(NotImplementedError) as e:
        MECHANISMS[mechanism](engine, plain_outputs)
    assert mechanism in str(e.value) and "loop_passes" in str(e.value)


def test_generate_runs_the_contiguous_cache(engine):
    """``generate()`` is the contiguous cache's: its greedy tokens are the
    serving engine's."""
    q = _requests(1, seed=12, new=(8, 9))[0]
    out = np.asarray(engine.generate(q.input_ids[None], max_new_tokens=8))
    (r,) = engine.serving(**SERVE_KW).run([q])
    assert list(out[0, len(q.input_ids):]) == list(r.output_ids)
