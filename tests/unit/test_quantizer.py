"""Quantizer op tests (reference tests/unit/ops/quantizer/ — kernel vs
python-reference methodology) plus the ZeRO++ quantized collectives."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.quantizer import (dequantize_blockwise, quantize_blockwise,
                                         quantized_all_gather,
                                         quantized_reduce_scatter)
from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh, shard_map_unchecked


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("shape", [(256,), (1000,), (64, 48), (3, 5, 7)])
def test_quant_roundtrip_error_bounded(bits, shape):
    rng = np.random.default_rng(0)
    x = rng.standard_normal(shape).astype(np.float32)
    q, s = quantize_blockwise(jnp.asarray(x), block=128, bits=bits)
    y = np.asarray(dequantize_blockwise(q, s, shape, jnp.float32,
                                        block=128, bits=bits))
    # symmetric quant: |err| <= scale/2 per element, scale = amax/qmax per block
    qmax = 127 if bits == 8 else 7
    assert y.shape == x.shape
    max_scale = np.abs(x).max() / qmax
    assert np.abs(y - x).max() <= max_scale * 0.5 + 1e-7


def test_quant_exact_zeros_and_extremes():
    x = jnp.asarray([0.0] * 128 + [1.0, -1.0] + [0.0] * 126)
    q, s = quantize_blockwise(x, block=128, bits=8)
    y = dequantize_blockwise(q, s, x.shape, jnp.float32, block=128, bits=8)
    np.testing.assert_allclose(np.asarray(y)[:128], 0.0)
    # block extremes are reproduced exactly (scale = amax/qmax)
    np.testing.assert_allclose(np.asarray(y)[128], 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[129], -1.0, rtol=1e-6)


def test_int4_packs_half_the_bytes():
    x = jnp.asarray(np.random.default_rng(1).standard_normal(512), jnp.float32)
    q8, _ = quantize_blockwise(x, block=128, bits=8)
    q4, _ = quantize_blockwise(x, block=128, bits=4)
    assert q4.size == q8.size // 2


def test_quantized_all_gather_matches_fp32_gather():
    mesh = initialize_mesh(MeshLayout(dp=8))
    rng = np.random.default_rng(2)
    x = rng.standard_normal((64, 16)).astype(np.float32)

    fn = shard_map_unchecked(
        functools.partial(quantized_all_gather, axis_name="data", block=64),
        mesh, in_specs=(P("data"),), out_specs=P())
    y = np.asarray(fn(jnp.asarray(x)))
    err = np.abs(y - x)
    scale_bound = np.abs(x).max() / 127
    assert err.max() <= scale_bound * 0.5 + 1e-7


@pytest.mark.slow
def test_quantized_all_gather_gradient_is_reduce_scatter():
    """AD through the quantized gather: cotangent reduce-scatters back to the
    shard (sum over the replicas' contributions)."""
    mesh = initialize_mesh(MeshLayout(dp=8))
    x = np.arange(32, dtype=np.float32).reshape(32, 1)

    def inner(xs):
        # loss = sum(full^2)/2 is computed identically on every device;
        # d loss / d shard = psum_scatter(full) = 8 * full[shard] ≈ 8 * x
        return jax.grad(lambda s: jnp.sum(
            quantized_all_gather(s, "data", block=8) ** 2) / 2)(xs)

    g = shard_map_unchecked(inner, mesh, in_specs=(P("data"),),
                         out_specs=P("data"))(jnp.asarray(x))
    scale_bound = np.abs(x).max() / 127
    assert np.abs(np.asarray(g) - 8 * x).max() <= 8 * scale_bound * 0.5 + 1e-6


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.slow
def test_quantized_reduce_scatter_close_to_exact(bits):
    mesh = initialize_mesh(MeshLayout(dp=8))
    rng = np.random.default_rng(3)
    # per-device distinct gradients: simulate with a sharded input where each
    # row-block is one device's full gradient? Instead: reduce over 'data' of
    # a REPLICATED tensor — every device contributes the same grad, so the
    # exact answer is 8 * grad scattered.
    g = rng.standard_normal((64, 8)).astype(np.float32)

    fn = shard_map_unchecked(
        functools.partial(quantized_reduce_scatter, axis_name="data",
                          block=32, bits=bits),
        mesh, in_specs=(P(),), out_specs=P("data"))
    out = np.asarray(fn(jnp.asarray(g)))
    expect = 8.0 * g
    qmax = 127 if bits == 8 else 7
    tol = 8 * (np.abs(g).max() / qmax) * 0.5 + 1e-6
    assert out.shape == g.shape
    assert np.abs(out - expect).max() <= tol


@pytest.mark.slow
def test_hierarchical_reduce_scatter_sum_and_landing():
    """Two-hop qgZ primitive: (1) the result equals the full cross-group sum
    (within quant noise), (2) the landing layout is OUTER-MAJOR — device
    (i, j) owns chunk i*n_inner+j — matching GSPMD's partition order for a
    dim sharded P(('data_outer', 'data')) and the concatenation order of
    quantized_all_gather."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.ops.quantizer import hierarchical_quantized_reduce_scatter
    from deepspeed_tpu.parallel.mesh import shard_map_unchecked

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("do", "d"))
    rng = np.random.default_rng(0)
    L, K = 32, 3
    locals_ = rng.standard_normal((8, L, K)).astype(np.float32)

    f = shard_map_unchecked(
        lambda x: hierarchical_quantized_reduce_scatter(
            x, "d", "do", scatter_dim=0, block=16),
        mesh, in_specs=(P(("do", "d"), None),),
        out_specs=P(("do", "d"), None))
    # each device feeds its own [L, K] block, stacked along axis 0
    out = np.asarray(f(jnp.asarray(locals_.reshape(8 * L, K))))
    expected = locals_.sum(axis=0)          # [L, K]
    assert out.shape == expected.shape
    # shard_map reassembles device (i,j)'s output at chunk i*4+j under the
    # P(('do','d')) out-spec, so element-order equality proves the landing
    np.testing.assert_allclose(out, expected, atol=0.15 * np.abs(expected).max())
