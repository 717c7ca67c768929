"""The serving executor holds the weights as its decode program reads them
(``MeshExecutor``, PR 35): a leaf a layer where the paged forward walks the
layers in Python (a ``layer_pattern``), each leaf in the layout the compiled
tick asks for.  On the CPU the compiler asks for the layout a leaf has, so
the second half is driven here by answering in its place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

import deepspeed_tpu
from deepspeed_tpu.inference.execution import MeshExecutor, _lies_as
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM, get_config, init_params
from deepspeed_tpu.models.transformer import forward, per_layer_leaves
from deepspeed_tpu.utils.compile_counter import compile_counter

SERVE_KW = dict(b_slots=3, page_size=8, max_model_len=96)
_count = compile_counter()

CONFIGS = {
    # layers of two kinds, walked in Python: a leaf a layer
    "layer_pattern": lambda: get_config(
        "mimo-v2.5", num_layers=7, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
        window_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
        window_size=16, num_experts=16, moe_experts_held=4, moe_top_k=3,
        vocab_size=256, max_seq_len=512, dtype=jnp.float32),
    # one scanned stack: held as it is
    "scanned": lambda: get_config(
        "opt-1.3b", num_layers=2, hidden_size=64, intermediate_size=128,
        num_heads=4, vocab_size=256, max_seq_len=128, dtype=jnp.float32),
}


def _engine(kind):
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    cfg = CONFIGS[kind]()
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(
        model=CausalLM(cfg), params=params, dtype="fp32",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))
    return cfg, params, engine


def _requests(n, seed=0):
    """Prompts of 17 to 30 tokens: one prefill bucket, so a warm engine has
    every program these compile."""
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", arrival_time=0.0, max_new_tokens=5,
                    input_ids=rng.integers(0, 256, (int(rng.integers(17, 31)),)
                                           ).astype(np.int32))
            for i in range(n)]


_FORWARD = {}


def _forward(cfg):
    return _FORWARD.setdefault(cfg, jax.jit(
        lambda p, toks: forward(cfg, p, toks)))


def _greedy(cfg, params, request, pad=40):
    """The request's tokens by the uncached forward over ``params``: one
    program a model, the ids padded behind (a causal model's logits at a
    position do not see what follows it)."""
    fwd = _forward(cfg)
    ids = [int(t) for t in request.input_ids]
    for _ in range(request.max_new_tokens):
        toks = np.zeros((1, pad), np.int32)
        toks[0, :len(ids)] = ids
        ids.append(int(jnp.argmax(fwd(params, toks)[0, len(ids) - 1])))
    return ids[len(request.input_ids):]


def _transposed(self, params, adapters):
    """In the compiler's place: every leaf of two or more axes with its two
    minor-most swapped, as the v5e asks for ``wq``."""
    def fmt(x):
        order = tuple(range(x.ndim))
        if x.ndim >= 2:
            order = order[:-2] + (order[-1], order[-2])
        return Format(Layout(major_to_minor=order), x.sharding)

    return jax.tree_util.tree_map(fmt, params)


@pytest.mark.parametrize("kind", list(CONFIGS))
def test_placed_tree_gives_the_stacked_trees_tokens(kind):
    cfg, stacked, engine = _engine(kind)
    sv = engine.serving(**SERVE_KW)
    placed = sv.params
    hybrid = kind == "layer_pattern"
    wq = placed["layers"]["window_moe"]["wq"] if hybrid \
        else placed["layers"]["wq"]
    assert isinstance(wq, tuple) == hybrid
    if hybrid:
        # the expert stacks stay whole, every other leaf is a layer's own
        assert not isinstance(placed["layers"]["window_moe"]["w_up"], tuple)
        assert len(wq) == 5 and wq[0].shape == stacked["layers"][
            "window_moe"]["wq"].shape[1:]
    requests = _requests(3)
    for r, q in zip(sv.run(requests), requests):
        assert list(r.output_ids) == _greedy(cfg, stacked, q)
    # and the forward reads the placed tree as it reads the stacked one
    toks = jnp.asarray([requests[0].input_ids], jnp.int32)
    np.testing.assert_array_equal(np.asarray(_forward(cfg)(placed, toks)),
                                  np.asarray(_forward(cfg)(stacked, toks)))


@pytest.mark.parametrize("kind", list(CONFIGS))
def test_update_params_with_a_stacked_tree_compiles_nothing(kind):
    cfg, stacked, engine = _engine(kind)
    sv = engine.serving(**SERVE_KW)
    ex = sv._exec
    sv.run(_requests(2))                               # every program warm
    new = jax.block_until_ready(jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: x * 1.01, p))(stacked))
    base = _count()
    sv.update_params(new)
    assert _count() - base == 0
    assert jax.tree_util.tree_structure(ex.params) == ex._param_treedef
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        _lies_as, ex.params, ex._param_formats)))
    requests = _requests(2, seed=1)
    out = sv.run(requests)
    assert _count() - base == 0                        # nor does serving it
    for r, q in zip(out, requests):
        assert list(r.output_ids) == _greedy(cfg, new, q)
    # the placed tree itself is taken too (fuse_adapter, a restart's carry)
    base = _count()          # (_greedy's forward may have compiled above)
    sv.update_params(sv.params)
    assert _count() - base == 0
    with pytest.raises(ValueError, match="structure differs"):
        sv.update_params({"layers": new["layers"]})


@pytest.mark.parametrize("kind", list(CONFIGS))
def test_the_engine_reads_the_placed_tree_after_serving(kind):
    cfg, stacked, engine = _engine(kind)
    sv = engine.serving(**SERVE_KW)
    assert engine.params is sv.params is sv._exec.params
    ids = np.asarray([_requests(1)[0].input_ids])
    np.testing.assert_allclose(          # one jitted, the other not
        np.asarray(engine(jnp.asarray(ids))),
        np.asarray(_forward(cfg)(stacked, jnp.asarray(ids))), atol=1e-5)
    if kind == "scanned":
        out = np.asarray(engine.generate(ids, max_new_tokens=5))
        assert list(out[0, ids.shape[1]:]) == _greedy(cfg, stacked,
                                                      _requests(1)[0])
    else:
        with pytest.raises(NotImplementedError, match="layer_pattern"):
            engine.generate(ids, max_new_tokens=5)
    # a second engine over the handed-back tree finds it placed, and what
    # the first compiled ahead of time to place it
    base = _count()
    again = engine.serving(**SERVE_KW)
    assert _count() - base == 0
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(again.params),
        jax.tree_util.tree_leaves(sv.params)))
    assert again._exec.mesh_info()["weight_leaves_split"] == 0


@pytest.mark.parametrize("kind", list(CONFIGS))
def test_mesh_info_reports_what_the_placement_did(kind):
    cfg, stacked, engine = _engine(kind)
    info = engine.serving(**SERVE_KW)._exec.mesh_info()
    cut = len(jax.tree_util.tree_leaves(
        per_layer_leaves(cfg, stacked)[0])) - len(
            jax.tree_util.tree_leaves(stacked))
    if kind == "layer_pattern":
        # every leaf of the three groups but the expert layers' six stacks
        assert info["weight_leaves_split"] == sum(
            1 for lp in stacked["layers"].values() for k in lp) - 6
        assert cut > 0
    else:
        assert info["weight_leaves_split"] == cut == 0
    # the CPU's compiler asks for the layout a leaf has
    assert info["weight_leaves_relaid"] == info["weight_bytes_relaid"] == 0


@pytest.mark.parametrize("kind", list(CONFIGS))
def test_leaves_lie_in_the_layouts_the_tick_asks_for(kind, monkeypatch):
    """The layouts the compiled tick asks for, answered here in the
    compiler's place: the tree is copied into them once, every program is
    compiled against them and serves the same tokens, and an update lands
    on them and compiles nothing."""
    monkeypatch.setattr(MeshExecutor, "_compile_tick_formats", _transposed)
    cfg, stacked, engine = _engine(kind)
    # another geometry than the other cases': what they found is kept a
    # process by what the program is made from
    sv = engine.serving(b_slots=2, page_size=8, max_model_len=80)
    ex = sv._exec
    leaves = jax.tree_util.tree_leaves(ex.params)
    wide = [x for x in leaves if x.ndim >= 2]
    info = ex.mesh_info()
    assert info["weight_leaves_relaid"] == len(wide) > 0
    assert info["weight_bytes_relaid"] == sum(x.nbytes for x in wide)
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        _lies_as, ex.params, ex._param_formats)))
    assert all(tuple(x.format.layout.major_to_minor)[-2:]
               == (x.ndim - 1, x.ndim - 2) for x in wide)
    requests = _requests(3, seed=2)
    for r, q in zip(sv.run(requests), requests):
        assert list(r.output_ids) == _greedy(cfg, stacked, q)
    assert ex._decode_prog._cache_size() == 1
    new = jax.block_until_ready(jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: x * 1.01, p))(stacked))
    base = _count()
    sv.update_params(new)
    out = sv.run(_requests(2, seed=3))
    assert _count() - base == 0
    assert ex._decode_prog._cache_size() == 1
    for r, q in zip(out, _requests(2, seed=3)):
        assert list(r.output_ids) == _greedy(cfg, new, q)
