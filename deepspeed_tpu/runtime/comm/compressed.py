"""Error-feedback 1-bit compressed gradient collective.

Reference mechanism: ``deepspeed/runtime/comm/nccl.py:54``
(``compressed_allreduce`` — sign compression + per-chunk scale + persistent
error feedback), used by the 1-bit optimizer family
(``runtime/fp16/onebit/adam.py:308``, docs claim up to 26x comm reduction).

TPU-first redesign.  The engine's normal DP gradient reduction is *implicit*
(XLA inserts it against sharding constraints), and an implicit collective
cannot change wire format.  So the 1-bit path computes LOCAL gradients inside
one fully-manual ``shard_map`` region over the data axis and performs the
compressed exchange explicitly:

  1. corrected = local_grad + error           (error feedback)
  2. per-block scale = mean(|corrected|)      (fp32, one per `block` elems)
  3. signs packed 8-per-byte                  (uint8 wire tensor)
  4. all_gather(packed signs), all_gather(scales) over 'data'
  5. decode each peer, average -> approximate mean gradient
  6. error = corrected - decode(own message)  (what compression lost)

Wire bytes per element: 1/8 (signs) + 4/block (scales) ≈ 0.14 B at block=256
vs 4 B fp32 — the reference's ~26x.  The uint8 all-gather is structurally
checkable in the compiled HLO (like the ZeRO++ tests do for s8).

The engine engages this path for ``optimizer.type`` one of
OneBitAdam / OneBitLamb / ZeroOneAdam with plain Adam/LAMB momentum math on
the compressed-averaged gradient (documented divergence: the reference
compresses the *momentum* after a warmup freeze; compressing the gradient
keeps the same wire format + error-feedback dynamics and composes with the
SPMD engine without forking the optimizer state across workers).  Before
``freeze_step`` (the reference's warmup) gradients are exchanged in full
precision.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

DEFAULT_BLOCK = 256
_BITS = np.asarray([1, 2, 4, 8, 16, 32, 64, 128], np.uint8)


def pack_signs(signs: jax.Array) -> jax.Array:
    """bool [n] (n % 8 == 0) -> uint8 [n/8], bit i = element 8k+i."""
    b = signs.reshape(-1, 8).astype(jnp.uint8)
    return (b * jnp.asarray(_BITS)).sum(axis=1).astype(jnp.uint8)


def unpack_signs(packed: jax.Array) -> jax.Array:
    """uint8 [m] -> f32 [m*8] of ±1."""
    bits = (packed[:, None] & jnp.asarray(_BITS)) > 0
    return jnp.where(bits, 1.0, -1.0).astype(jnp.float32).reshape(-1)


def _pad_len(n: int, block: int) -> int:
    lcm = np.lcm(block, 8)
    return int(-(-n // lcm) * lcm)


def ef_compress(flat: jax.Array, error: jax.Array, block: int = DEFAULT_BLOCK):
    """flat f32 [npad] + error [npad] -> (packed u8, scales f32, new_error).

    Scale is the per-block mean magnitude of the corrected tensor, so
    decode(message) = sign * scale is the 1-bit quantization with minimal
    L1 error per block (the reference's convention, nccl.py:91).
    """
    corrected = flat + error
    nb = corrected.shape[0] // block
    blocks = corrected.reshape(nb, block)
    scales = jnp.mean(jnp.abs(blocks), axis=1)  # [nb]
    signs = corrected >= 0
    packed = pack_signs(signs)
    decoded = (jnp.where(signs.reshape(nb, block), 1.0, -1.0)
               * scales[:, None]).reshape(-1)
    new_error = corrected - decoded
    return packed, scales, new_error


def ef_decode(packed: jax.Array, scales: jax.Array, block: int) -> jax.Array:
    signs = unpack_signs(packed)  # [npad]
    return (signs.reshape(-1, block) * scales[:, None]).reshape(-1)


def compressed_mean(flat: jax.Array, error: jax.Array, axis: str,
                    block: int = DEFAULT_BLOCK) -> Tuple[jax.Array, jax.Array]:
    """INSIDE a manual region: EF-compressed mean of ``flat`` over ``axis``.

    Returns (approx mean over workers, new local error)."""
    packed, scales, new_error = ef_compress(flat, error, block)
    all_packed = lax.all_gather(packed, axis)   # [w, n/8] uint8 on the wire
    all_scales = lax.all_gather(scales, axis)   # [w, nb]  fp32 (tiny)
    decoded = jax.vmap(lambda p, s: ef_decode(p, s, block))(all_packed, all_scales)
    return decoded.mean(axis=0), new_error


def init_error_tree(params: Any, mesh, block: int = DEFAULT_BLOCK) -> Any:
    """Per-worker error buffers: one flat f32 [w * npad] leaf per param leaf,
    sharded over the data axis so each worker owns its own slice."""
    from ...parallel.mesh import BATCH_AXES, axis_size

    w = axis_size(mesh, BATCH_AXES)

    def one(x):
        npad = _pad_len(x.size, block)
        return jnp.zeros((w * npad,), jnp.float32)

    return jax.tree_util.tree_map(one, params)


def error_tree_specs(params: Any) -> Any:
    from ...parallel.mesh import BATCH_AXES

    return jax.tree_util.tree_map(lambda _: P(BATCH_AXES), params)


def make_compressed_grad_fn(accumulate, mesh, gas: int, freeze_step: int,
                            param_template: Any, block: int = DEFAULT_BLOCK):
    """Build the manual-region gradient function for the 1-bit path.

    ``accumulate`` is ``engine.make_grad_accumulator(grad_of_batch, gas)`` —
    the shared microbatch scan.  Returns
    ``fn(work_params, scaler, batch_window, rng, error, step)``
    -> (mean_grads, losses, new_error); ``batch_window`` is [gas, B_global,...].
    Requires a pure-DP mesh (engine validates).
    """
    from ...parallel.mesh import manual_region, shard_map_unchecked
    from ...parallel.mesh import BATCH_AXES

    pads = jax.tree_util.tree_map(lambda x: _pad_len(x.size, block),
                                  param_template)

    def region(work, scaler, window, rng, error, step):
        local_grads, losses, _ = accumulate(work, scaler, window, rng)

        def full_precision():
            g = jax.tree_util.tree_map(
                lambda x: lax.pmean(x, BATCH_AXES), local_grads)
            return g, error

        def one_bit():
            # The accumulated grads are loss-scale*gas-scaled; the error
            # buffer must carry residuals in UNSCALED units or every dynamic
            # loss-scale change would mis-weight the carried error vs the
            # current gradients.  Compress unscaled, re-scale the mean so
            # apply_update's single unscale stays correct.
            inv = (1.0 / (scaler.loss_scale * gas)).astype(jnp.float32)
            flat_grads = jax.tree_util.tree_map(
                lambda g, npad: jnp.pad(g.ravel() * inv, (0, npad - g.size)),
                local_grads, pads)
            out = jax.tree_util.tree_map(
                lambda f, e: compressed_mean(f, e, BATCH_AXES, block),
                flat_grads, error)
            is_pair = lambda t: isinstance(t, tuple)  # noqa: E731
            means = jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_pair)
            errs = jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_pair)
            g = jax.tree_util.tree_map(
                lambda m, ref: (m[:ref.size] / inv).reshape(ref.shape), means,
                local_grads)
            return g, errs

        grads, new_error = lax.cond(step < freeze_step, full_precision, one_bit)
        losses = lax.pmean(losses, BATCH_AXES)
        return grads, losses, new_error

    rep = jax.tree_util.tree_map(lambda _: P(), param_template)
    err_specs = error_tree_specs(param_template)
    # window leaves are [gas, B_global, ...]: shard dim 1 over the DP axes
    # (prefix spec broadcasts over every batch leaf)
    sm = shard_map_unchecked(
        region, mesh,
        in_specs=(rep, P(), P(None, BATCH_AXES), P(), err_specs, P()),
        out_specs=(rep, P(), err_specs))

    def fn(work, scaler, window, rng, error, step):
        with manual_region():
            return sm(work, scaler, window, rng, error, step)

    return fn
