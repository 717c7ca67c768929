"""PLD + eigenvalue (reference runtime/progressive_layer_drop.py,
runtime/eigenvalue.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.runtime.eigenvalue import Eigenvalue, hvp
from deepspeed_tpu.runtime.progressive_layer_drop import (
    ProgressiveLayerDrop, pld_keep_mask, pld_theta_at)

from .simple_model import SimpleModel, random_batch


@pytest.fixture(autouse=True)
def _fresh_mesh():
    mesh_mod.reset_mesh()
    yield
    mesh_mod.reset_mesh()


# ------------------------------------------------------------------ PLD --

def test_pld_schedule_decays_to_theta():
    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.01)
    assert pld.get_theta() == 1.0
    v0 = pld.update_state(0)
    v1000 = pld.update_state(1000)
    assert v0 == pytest.approx(1.0)
    assert 0.5 < v1000 < 1.0
    assert pld.update_state(10 ** 6) == pytest.approx(0.5, abs=1e-6)
    assert pld.get_state()["progressive_layer_drop"] is True


def test_pld_keep_mask_depth_scaled():
    theta = jnp.float32(0.5)
    keeps = np.stack([
        np.asarray(pld_keep_mask(jax.random.PRNGKey(i), 8, theta))
        for i in range(300)])
    rate = keeps.mean(0)
    # first layer keeps with p≈1-1/8*0.5≈0.94; last with p≈0.5
    assert rate[0] > rate[-1]
    assert abs(rate[-1] - 0.5) < 0.1


def test_pld_theta_traced():
    t = pld_theta_at(jnp.int32(0), 0.5, 0.001)
    assert float(t) == pytest.approx(1.0)


@pytest.mark.slow
def test_pld_training_end_to_end():
    model = CausalLM("tiny", max_seq_len=64)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "progressive_layer_drop": {"enabled": True, "theta": 0.6,
                                   "gamma": 0.01},
        "bf16": {"enabled": True},
    })
    assert engine.progressive_layer_drop is not None
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, model.config.vocab_size,
        (engine.train_batch_size, 16)).astype(np.int32)}
    losses = [float(engine.train_batch(batch=dict(batch))) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert engine.progressive_layer_drop.get_theta() < 1.0
    # eval path ignores PLD (deterministic, full depth)
    assert np.isfinite(float(engine.eval_batch(batch=dict(batch))))


# ------------------------------------------------------------ eigenvalue --

def test_hvp_matches_dense_hessian():
    """Quadratic loss: H is known exactly."""
    A = jnp.asarray(np.random.default_rng(0).normal(size=(4, 4)), jnp.float32)
    H = A @ A.T + 4.0 * jnp.eye(4)   # SPD

    def loss_fn(p, batch, rng):
        return 0.5 * p["w"] @ H @ p["w"]

    p = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(4,)),
                          jnp.float32)}
    v = {"w": jnp.asarray([1.0, 0.0, 0.0, 0.0], jnp.float32)}
    hv = hvp(loss_fn, p, None, None, v)
    np.testing.assert_allclose(np.asarray(hv["w"]), np.asarray(H[:, 0]),
                               rtol=1e-5)


def test_power_iteration_finds_lambda_max():
    A = jnp.asarray(np.random.default_rng(2).normal(size=(6, 6)), jnp.float32)
    H = A @ A.T

    def loss_fn(p, batch, rng):
        return 0.5 * p["w"] @ H @ p["w"]

    p = {"w": jnp.zeros((6,), jnp.float32)}
    est = Eigenvalue(max_iter=200, tol=1e-5)
    lam, per_leaf = est.compute_eigenvalue(loss_fn, p, None)
    true = float(np.linalg.eigvalsh(np.asarray(H)).max())
    assert lam == pytest.approx(true, rel=1e-2)
    assert "w" in per_leaf


def test_engine_compute_eigenvalue():
    engine, _, _, _ = deepspeed_tpu.initialize(model=SimpleModel(16), config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "eigenvalue": {"enabled": True, "max_iter": 10},
        "bf16": {"enabled": True},
    })
    lam, per_leaf = engine.compute_eigenvalue(
        random_batch(engine.train_batch_size, 16, 0))
    assert np.isfinite(lam)
    assert per_leaf and all(np.isfinite(v) for v in per_leaf.values())


# ---------------------------------------------------------------------------
# SparseTensor (reference runtime/sparse_tensor.py)


def test_sparse_tensor_roundtrip():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.runtime.sparse_tensor import (SparseTensor,
                                                     from_embedding_grad)

    V, d = 16, 4
    tokens = jnp.asarray([1, 3, 3, 7], jnp.int32)
    cot = jnp.arange(4 * d, dtype=jnp.float32).reshape(4, d)
    st = from_embedding_grad(tokens, cot, V)
    dense = np.asarray(jax.jit(lambda s: s.to_dense())(st))
    ref = np.zeros((V, d), np.float32)
    for t, g in zip(np.asarray(tokens), np.asarray(cot)):
        ref[t] += g  # duplicates sum — scatter-add semantics
    np.testing.assert_array_equal(dense, ref)
    both = st.add(st)
    np.testing.assert_array_equal(np.asarray(both.to_dense()), 2 * ref)
    assert st.nbytes < V * d * 4  # sparser than dense for few rows


def test_sparse_allreduce_over_data_axis():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh
    from deepspeed_tpu.runtime.sparse_tensor import (SparseTensor,
                                                     sparse_allreduce)

    mesh_mod.reset_mesh()
    mesh = initialize_mesh(MeshLayout(dp=8))
    V, d, N = 32, 4, 8  # N rows per worker
    rows = jnp.tile(jnp.arange(8, dtype=jnp.int32), 8)          # [64]
    values = jnp.ones((64, d), jnp.float32)

    def region(r, v):
        st = sparse_allreduce(SparseTensor(r, v, dense_rows=V), "data")
        return st.to_dense()

    f = mesh_mod.shard_map_unchecked(
        region, mesh, in_specs=(P(("data_outer", "data", "expert")),
                                P(("data_outer", "data", "expert"), None)),
        out_specs=P())
    with mesh_mod.manual_region():
        dense = np.asarray(f(rows, values))
    # every worker contributed ones on rows 0..7 -> each row sums to 8·... 
    np.testing.assert_array_equal(dense[:8], np.full((8, d), 8.0))
    np.testing.assert_array_equal(dense[8:], np.zeros((V - 8, d)))
    mesh_mod.reset_mesh()


def test_sparse_gradients_config_rejected():
    import deepspeed_tpu
    import pytest as _pytest

    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    with _pytest.raises(NotImplementedError, match="sparse_gradients"):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "sparse_gradients": True})
