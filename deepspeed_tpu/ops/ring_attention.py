"""Ring attention — sequence/context parallelism over the 'seq' mesh axis.

The reference snapshot has NO sequence parallelism (SURVEY §5: predates
DeepSpeed-Ulysses; its long-sequence story is block-sparse attention).  The
TPU build treats SP as a first-class mesh axis: queries stay resident on
their shard while K/V blocks rotate around the ring via ``lax.ppermute``
(nearest-neighbor ICI hops), and per-block attention results merge with a
running log-sum-exp — attention over sequences N× longer than one chip's
score memory would allow, with compute overlapping the rotation.

The inner block is the PALLAS FLASH KERNEL (``impl='flash'``, the default
whenever the local shard is tile-aligned): per ring step nothing larger
than the kernel's [block_q, block_k] tiles materializes, so per-device
score memory is O(tile²) — independent of S — and the remaining
long-context footprint is the O(S) rotated K/V that scan-AD holds for
backward.  The merge consumes the kernel's native lse output through an
lse-differentiable VJP (the plain kernel's dropped-lse shortcut would
corrupt gradients here).  The einsum fallback ([S/N, S/N] fp32 scores per
step) remains for tile-unaligned shards.

Causal structure: the diagonal block is ring step 0 (outside the scan) and
runs the causal kernel; every scanned block is strictly past or strictly
future, so the scan runs the NON-causal kernel and kills fully-future
blocks by forcing their lse to -1e30 (merge weight underflows to zero —
uniform SPMD control flow, no per-device branching).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


def _block_attn(q, k, v, q_off, k_off, sm_scale, causal):
    """q [B,Sq,Hq,hd], k/v [B,Sk,Hkv,hd] -> (o [B,Sq,Hq,hd], lse [B,Hq,Sq]).

    Grouped-query attention stays grouped: q folds to [B,Sq,Hkv,G,hd] and the
    einsums contract against the Hkv-head K/V directly — no materialized
    repeat, so the ring carries (and rotates) only the true KV bytes."""
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * sm_scale
    if causal:
        rows = q_off + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        cols = k_off + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where((rows >= cols)[None, None, None], s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)                     # [B,Hkv,G,Sq]
    p = jnp.exp(s - lse[..., None]).astype(q.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(B, Sq, Hq, hd)
    return o, lse.reshape(B, Hq, Sq)


def _flash_ok(Sl: int, hd: int) -> bool:
    """Tile alignment for the Pallas inner block (kernel needs 128-multiple
    sequence tiles; lane dim rides hd directly)."""
    return Sl % 128 == 0


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = True,
                   sm_scale: Optional[float] = None, impl: str = "auto"):
    """Runs INSIDE shard_map: q/k/v are the local sequence shards
    [B, S_local, H, hd]; returns the local output shard.

    ``impl``: 'flash' (Pallas inner block, O(tile²) score memory), 'einsum'
    (the [Sl,Sl] fp32 fallback), or 'auto' (flash when tile-aligned).
    """
    B, Sl, Hq, hd = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if impl == "auto":
        impl = "flash" if _flash_ok(Sl, hd) else "einsum"
    elif impl == "flash" and not _flash_ok(Sl, hd):
        raise ValueError(
            f"ring impl='flash' requires a 128-multiple local shard, got "
            f"S_local={Sl}")
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    if impl == "flash":
        from .pallas.flash_attention import flash_attention

        def block(q, k, v, block_causal):
            # lse-differentiable kernel: the merge weights depend on lse
            return flash_attention(q, k, v, causal=block_causal,
                                   sm_scale=sm_scale, return_lse=True)

    def merge(o, lse, o_b, lse_b):
        new_lse = jnp.logaddexp(lse, lse_b)
        w_old = jnp.exp(lse - new_lse)           # [B,H,Sq]
        w_new = jnp.exp(lse_b - new_lse)
        o = (o * jnp.swapaxes(w_old, 1, 2)[..., None]
             + o_b.astype(jnp.float32) * jnp.swapaxes(w_new, 1, 2)[..., None])
        return o, new_lse

    # Step 0 (the local K/V block) runs outside the scan so the ring does
    # exactly n-1 rotations — the carried K/V after the last compute is
    # never permuted just to be discarded.  It is also the ONLY causal
    # block: every scanned block is strictly past or strictly future.
    if impl == "flash":
        o_b, lse_b = block(q, k, v, causal)
    else:
        o_b, lse_b = _block_attn(q, k, v, me * Sl, me * Sl, sm_scale, causal)
    # fp32 accumulator: the running rescale-and-add compounds rounding error
    # across ring steps if carried in bf16; cast once at the end
    o0 = o_b.astype(jnp.float32)
    lse0 = lse_b

    def step(carry, r):
        o, lse, k_cur, v_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (me - r) % n                       # whose K/V block we hold
        if impl == "flash":
            o_b, lse_b = block(q, k_cur, v_cur, False)
            if causal:
                # fully-future block: merge weight underflows to zero (the
                # zero cotangent likewise zeroes its backward contribution)
                lse_b = jnp.where(src < me, lse_b, -1e30)
        else:
            o_b, lse_b = _block_attn(q, k_cur, v_cur, me * Sl, src * Sl,
                                     sm_scale, causal)
        o, lse = merge(o, lse, o_b, lse_b)
        return (o, lse, k_cur, v_cur), None

    (o, _, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v), jnp.arange(1, n))
    return o.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, batch_axes, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           seq_axis: str = "seq", head_axis: str = "model",
                           impl: str = "auto"):
    """shard_map wrapper: q/k/v are global [B, S, H, hd] arrays; batch rides
    ``batch_axes``, sequence is split over ``seq_axis``, heads over
    ``head_axis``."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import shard_map_unchecked

    spec = P(batch_axes, seq_axis, head_axis, None)
    fn = shard_map_unchecked(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                          sm_scale=sm_scale, impl=impl),
        mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
