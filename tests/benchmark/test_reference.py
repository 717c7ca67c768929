"""reference.py against models/transformer.py, both configurations' blocks
at a small size, float32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference, system


@pytest.mark.parametrize("name", ["opt-1.3b", "pythia-1.4b-d10"])
def test_reference_matches_model_forward(name):
    from deepspeed_tpu.models import forward, init_params

    cfg = system.transformer_config(
        system.load_json("configs", name + ".json"), rehearse=True)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(3))
    # biases and norm offsets start at 0 and 1: randomise so a dropped bias
    # or a swapped norm would show
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32))
    with jax.default_matmul_precision("highest"):
        got = forward(cfg, params, toks, attn_impl="xla")
    for b in range(2):
        ref = reference.reference_logits(cfg, params, toks[b])
        assert reference.rel_err(got[b], ref) < 2e-5


def test_reference_refuses_other_blocks():
    from deepspeed_tpu.models import get_config

    with pytest.raises(NotImplementedError):
        reference.reference_logits(get_config("tiny"), {}, jnp.zeros((4,), jnp.int32))


def test_rel_err_flags_non_finite():
    assert reference.rel_err(np.array([np.nan]), np.array([1.0])) == float("inf")
    assert reference.rel_err(np.array([1.5]), np.array([2.0])) == 0.25
