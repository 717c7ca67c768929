"""Collective facade tests — parity with reference tests/unit/comm/test_dist.py."""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu.comm as dist
from deepspeed_tpu.parallel import initialize_mesh
from deepspeed_tpu.parallel.mesh import shard_map_unchecked


def _shmap(mesh, fn, in_specs, out_specs):
    return jax.jit(shard_map_unchecked(fn, mesh=mesh, in_specs=in_specs,
                                    out_specs=out_specs))


def test_all_reduce_sum():
    mesh = initialize_mesh()  # 8-way data
    x = jnp.arange(8.0)

    f = _shmap(mesh, lambda v: dist.all_reduce(v, axis=("data", "expert")),
               P(("data", "expert")), P(("data", "expert")))
    out = f(x)
    # each shard (1 elem) is replaced by global sum = 28
    np.testing.assert_allclose(np.asarray(out), np.full((8,), 28.0))


def test_all_gather_tiled():
    mesh = initialize_mesh()
    x = jnp.arange(8.0)
    f = _shmap(mesh, lambda v: dist.all_gather(v, axis=("data", "expert")),
               P(("data", "expert")), P())
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))


def test_reduce_scatter():
    mesh = initialize_mesh()
    x = jnp.ones((8, 8))
    # per-rank input [1,8]; rank r keeps the sum of column-block r -> global [8,1]
    f = _shmap(mesh, lambda v: dist.reduce_scatter(v, axis=("data", "expert"), scatter_dim=1),
               P(("data", "expert"), None), P(("data", "expert"), None))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 8.0))


def test_all_to_all():
    mesh = initialize_mesh()
    x = jnp.arange(64.0).reshape(8, 8)
    # rank r sends column block j to rank j; result is the block transpose,
    # globally laid out as [64, 1] row-sharded (concat along dim 0 per rank)
    f = _shmap(mesh, lambda v: dist.all_to_all(v, axis=("data", "expert"),
                                               split_dim=1, concat_dim=0),
               P(("data", "expert"), None), P(("data", "expert"), None))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.arange(64.0).reshape(8, 8).T.reshape(64, 1))


def test_ppermute_ring():
    mesh = initialize_mesh()
    x = jnp.arange(8.0)
    f = _shmap(mesh, lambda v: dist.send_recv_next(v, axis="data"),
               P(("data", "expert")), P(("data", "expert")))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))


def test_axis_index_and_size():
    mesh = initialize_mesh()

    def body(v):
        idx = dist.axis_index(("data", "expert"))
        return v * 0 + idx

    f = _shmap(mesh, body, P(("data", "expert")), P(("data", "expert")))
    np.testing.assert_allclose(np.asarray(f(jnp.zeros(8))), np.arange(8))


def test_init_distributed_single_process():
    dist.init_distributed()
    assert dist.is_initialized()
    assert dist.get_world_size() == 1 and dist.get_rank() == 0
    dist.barrier()


def test_comms_logger_records_sizes():
    from deepspeed_tpu.runtime.config import CommsLoggerConfig

    dist.configure(CommsLoggerConfig(enabled=True))
    mesh = initialize_mesh()
    x = jnp.ones((8, 4), jnp.float32)
    f = _shmap(mesh, lambda v: dist.all_reduce(v, axis=("data", "expert")),
               P(("data", "expert"), None), P(("data", "expert"), None))
    f(x)  # trace records the op
    logger = dist.get_comms_logger()
    assert logger is not None and "all_reduce" in logger.comms_dict
    logger.log_all()
