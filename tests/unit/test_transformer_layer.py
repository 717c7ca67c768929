"""DeepSpeedTransformerLayer API (reference ops/transformer/transformer.py:296;
tests model tests/unit/ops/transformer/test_*)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerConfig,
                                           DeepSpeedTransformerLayer)


def _cfg(**kw):
    return DeepSpeedTransformerConfig(
        batch_size=2, hidden_size=32, intermediate_size=64, heads=4,
        attn_dropout_ratio=0.0, hidden_dropout_ratio=0.0,
        num_hidden_layers=2, initializer_range=0.02, training=False, **kw)


@pytest.mark.parametrize("pre_ln", [True, False], ids=["pre_ln", "post_ln"])
def test_layer_forward_shapes(pre_ln):
    layer = DeepSpeedTransformerLayer(_cfg(pre_layer_norm=pre_ln))
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    out = layer.apply(params, x)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())


def test_layer_matches_bert_block():
    """post-LN mode must be exactly the native encoder block the BERT
    injection path trains (one implementation, two surfaces)."""
    from deepspeed_tpu.models.transformer import _attend_full, _block

    layer = DeepSpeedTransformerLayer(_cfg(pre_layer_norm=False))
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    out = layer.apply(params, x)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (2, 8))
    ref = _block(layer.native, params, x.astype(layer.native.dtype), pos,
                 jax.random.PRNGKey(0),
                 _attend_full(layer.native, pos, "auto"))[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


def test_layer_is_bidirectional():
    layer = DeepSpeedTransformerLayer(_cfg())
    params = layer.init(jax.random.PRNGKey(0))
    x1 = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, 8, 32)))
    x2 = x1.copy()
    x2[0, -1] = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (32,)))
    o1 = np.asarray(layer.apply(params, jnp.asarray(x1)))
    o2 = np.asarray(layer.apply(params, jnp.asarray(x2)))
    assert not np.allclose(o1[0, 0], o2[0, 0])


def test_layer_initial_weights_and_return_tuple():
    layer = DeepSpeedTransformerLayer(
        _cfg(return_tuple=True),
        initial_weights={"wq": np.zeros((32, 32), np.float32)})
    params = layer.init(jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(params["wq"]), 0.0)
    out = layer.apply(params, jax.random.normal(jax.random.PRNGKey(1),
                                                (1, 4, 32)))
    assert isinstance(out, tuple) and out[0].shape == (1, 4, 32)


def test_layer_stochastic_mode_is_same_program():
    """stochastic_mode selects a CUDA schedule in the reference; under XLA
    both modes compile to the same math — accepted, not a behavior fork."""
    base = DeepSpeedTransformerLayer(_cfg())
    sto = DeepSpeedTransformerLayer(_cfg(stochastic_mode=True))
    params = base.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
    np.testing.assert_array_equal(np.asarray(base.apply(params, x)),
                                  np.asarray(sto.apply(params, x)))
