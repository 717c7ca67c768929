"""Flash attention for TPU (Pallas, MXU-tiled, online softmax).

The TPU-native replacement for the reference's fused attention kernels
(csrc/transformer/softmax_kernels.cu, csrc/transformer/inference/csrc/
softmax.cu "softmax_context") and the block-sparse path
(deepspeed/ops/sparse_attention/): one kernel covers dense causal attention
with O(S) memory; block-sparse patterns reduce to the same kernel with block
skipping (causal is the special case the trainer uses).

Layout: q [B, Hq, S, hd], k/v [B, Hkv, S, hd] (grouped-query: Hq % Hkv == 0 —
the kernel indexes the KV head directly, no materialized repeat).
Forward saves the log-sum-exp rows; backward runs two kernels (dq sweep over
KV blocks; dkv sweep over Q blocks) with the standard delta = rowsum(dO*O).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on v5e for hd=128-class shapes (best-of-3, causal B8 H14 S2048):
# 1024-tiles beat 256 by 1.9x fwd / 1.7x bwd; the FORWARD gains another ~25%
# with a full-row K block (bk=2048: the online-softmax carry disappears),
# while backward is fastest at 1024 — so fwd defaults to bk=2048 and the
# wrapper caps the bwd tiles at 1024.  _pick_block shrinks for short S.
# Tile choice is measured in the FULL remat train step, not in kernel
# isolation: an isolated fwd+bwd sweep preferred fwd block_q=512 by 11-25%,
# but the same tiles cost ~2.5% end-to-end (S=8192 llama bench, same
# thermal state) — the rematerialized fwd inside the backward schedules
# differently than a standalone chain.  Keep (1024, 2048) fwd + 1024 bwd.
import os as _os

def _env_block(name: str, default: int) -> int:
    """Tile override via env (read at import, so it binds when a program
    traces): lets a run A/B tile choices in the FULL remat train step, the
    only measurement that predicts end-to-end cost (see note above:
    isolated sweeps mislead)."""
    v = _os.environ.get(name, "").strip()
    if not v:
        return default
    try:
        iv = int(v)
    except ValueError as e:
        raise ValueError(f"{name}={v!r} is not an integer") from e
    if iv < 128 or iv % 128:
        raise ValueError(f"{name}={iv} must be a positive multiple of 128 "
                         "(MXU tile granularity)")
    return iv


DEFAULT_BLOCK_Q = _env_block("DS_TPU_FLASH_BLOCK_Q", 1024)
DEFAULT_BLOCK_K = _env_block("DS_TPU_FLASH_BLOCK_K", 2048)
# backward tiles: min(fwd tile, this) — the bwd kernels compile reliably at 1024
DEFAULT_BWD_BLOCK = _env_block("DS_TPU_FLASH_BWD_BLOCK", 1024)

from .common import (NEG_INF, parallel_semantics,  # noqa: E402
                     pick_block as _pick_block, resolve_interpret)

# The first three grid axes are independent in every kernel here; only the
# INNERMOST axis carries accumulator state (the K sweep in _fwd/_bwd_dq, the
# Q-and-group sweep in _bwd_dkv) and must stay 'arbitrary'.
_COMPILER_PARAMS = parallel_semantics(3, 1)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale: float, causal: bool,
                block_q: int, block_k: int, num_k: int, masked: bool = False):
    if masked:
        mask_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    should_run = True
    if causal:
        should_run = ki * block_k <= qi * block_q + block_q - 1
    if masked:
        live = mask_ref[qi, ki] != 0
        should_run = jnp.logical_and(should_run, live) if causal else live

    @pl.when(should_run)
    def _body():
        q, k, v = q_ref[:], k_ref[:], v_ref[:]    # native dtype into the MXU
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                              # [bq, bk] fp32
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_ref[:, :1]                         # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)     # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)               # [bq, 1]
        p = jnp.exp(s - m_new)                        # [bq, bk]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_k - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l_safe)
        lse_ref[:] = lse[:, 0][None, :]


def _mask_array(block_mask):
    """Hashable tuple-of-tuples (custom_vjp static arg) -> int32 array."""
    import numpy as _np

    return jnp.asarray(_np.asarray(block_mask, _np.int32))


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
         block_mask=None):
    B, Hq, S, hd = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    num_q, num_k = pl.cdiv(S, block_q), pl.cdiv(S, block_k)
    grid = (B, Hq, num_q, num_k)
    masked = block_mask is not None

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, num_k=num_k,
                               masked=masked)
    in_specs = [
            pl.BlockSpec((None, None, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
    ]
    operands = [q, k, v]
    if masked:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(_mask_array(block_mask))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, 1, block_q),
                         lambda b, h, qi, ki: (b, h, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*operands)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   sm_scale, causal, block_q, block_k, num_k,
                   masked: bool = False):
    if masked:
        mask_ref, dq_ref, acc_ref = rest
    else:
        dq_ref, acc_ref = rest
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    should_run = True
    if causal:
        should_run = ki * block_k <= qi * block_q + block_q - 1
    if masked:
        live = mask_ref[qi, ki] != 0
        should_run = jnp.logical_and(should_run, live) if causal else live

    @pl.when(should_run)
    def _body():
        q, k, v, do = q_ref[:], k_ref[:], v_ref[:], do_ref[:]
        lse = lse_ref[0, :][:, None]               # [bq, 1]
        delta = delta_ref[0, :][:, None]           # [bq, 1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale              # [bq, bk]
        acc_ref[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                          (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    sm_scale, causal, block_q, block_k, num_q, group,
                    masked: bool = False):
    # Grid head axis is the KV head; the innermost axis walks every
    # (q-head-in-group, q-block) pair so dk/dv accumulate in VMEM at
    # [B, Hkv, S, hd] — no group-times-larger HBM intermediate.
    if masked:
        mask_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    ki, j = pl.program_id(2), pl.program_id(3)
    qi = j % num_q

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    should_run = True
    if causal:
        should_run = qi * block_q + block_q - 1 >= ki * block_k
    if masked:
        live = mask_ref[qi, ki] != 0
        should_run = jnp.logical_and(should_run, live) if causal else live

    @pl.when(should_run)
    def _body():
        q, k, v, do = q_ref[:], k_ref[:], v_ref[:], do_ref[:]
        lse = lse_ref[0, :][:, None]
        delta = delta_ref[0, :][:, None]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(j == num_q * group - 1)
    def _finish():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, interpret, res, g,
         block_mask=None, dlse=None):
    q, k, v, out, lse = res
    do = g
    B, Hq, S, hd = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    num_q, num_k = pl.cdiv(S, block_q), pl.cdiv(S, block_k)
    masked = block_mask is not None
    mask_ops = [_mask_array(block_mask)] if masked else []
    mask_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] if masked else []

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    if dlse is not None:
        # lse cotangent folds into delta: d s_ij = p_ij (dp_ij - delta_i)
        # + p_ij dlse_i  ==  p_ij (dp_ij - (delta_i - dlse_i)) — so the
        # kernels run unchanged with a shifted delta (the ring-attention
        # merge differentiates through lse, unlike the plain path whose
        # lse is consumed only by checkpoint_name)
        delta = delta - dlse.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k=num_k,
                          masked=masked),
        grid=(B, Hq, num_q, num_k),
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((None, None, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, 1, block_q),
                         lambda b, h, qi, ki: (b, h, 0, qi)),
            pl.BlockSpec((None, None, 1, block_q),
                         lambda b, h, qi, ki: (b, h, 0, qi)),
        ] + mask_specs,
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse, delta, *mask_ops)

    # dk/dv accumulate per (kv-head, kv-block); the inner grid axis sweeps
    # all group*num_q (q-head, q-block) pairs so the group reduction happens
    # in the VMEM accumulator, not in an [B, Hq, S, hd] HBM intermediate.
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=num_q,
                          group=group, masked=masked),
        grid=(B, Hkv, num_k, num_q * group),
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, ki, j: (b, h * group + j // num_q, j % num_q, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, ki, j: (b, h, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, ki, j: (b, h, ki, 0)),
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, ki, j: (b, h * group + j // num_q, j % num_q, 0)),
            pl.BlockSpec((None, None, 1, block_q),
                         lambda b, h, ki, j: (b, h * group + j // num_q, 0, j % num_q)),
            pl.BlockSpec((None, None, 1, block_q),
                         lambda b, h, ki, j: (b, h * group + j // num_q, 0, j % num_q)),
        ] + mask_specs,
        out_specs=[
            pl.BlockSpec((None, None, block_k, hd), lambda b, h, ki, j: (b, h, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd), lambda b, h, ki, j: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, S, hd), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, S, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse, delta, *mask_ops)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

# The custom VJP is defined on a function whose PRIMAL OUTPUTS are (out, lse)
# — exactly the non-input residuals the backward needs.  Both are named with
# checkpoint_name INSIDE the vjp-fwd, as the residuals themselves, so a remat
# policy that pins q/k/v + attn_out + attn_lse lets the backward run WITHOUT
# re-executing the forward kernel.  A name on a value derived from ``out``
# does not do: the residual is ``out`` as the kernel wrote it, [B,H,S,hd],
# and remat cannot get that back from the model's [B,S,H*hd] view of it —
# on the v5e all four kernels of a layer still ran under "save_matmuls"
# while only the view was named (PERF.md §6, PR 38).
# Forward and backward take SEPARATE tile sizes: the fwd prefers a full-row K
# block (no online-softmax carry — measured ~25% faster at S=2048), while the
# bwd kernels are fastest (and compile reliably) at 1024.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret,
           bwd_block_q, bwd_block_k, block_mask=None):
    return _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                block_mask)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               bwd_block_q, bwd_block_k, block_mask=None):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                    block_mask)
    # names INSIDE the vjp-fwd so remat policies can pin the residuals
    # themselves (with the model-level q/k/v names)
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return (out, lse), (q, k, v, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, bwd_block_q,
               bwd_block_k, block_mask, res, g):
    do, _ = g  # lse is consumed only by checkpoint_name: zero cotangent
    return _bwd(sm_scale, causal, bwd_block_q, bwd_block_k, interpret, res,
                do, block_mask)


_flash.defvjp(_flash_fwd, _flash_bwd)


# Same kernels, but lse is a REAL (differentiable) output: the ring
# merge computes output weights from per-block lse, so its cotangent is
# nonzero — _flash would silently drop it (wrong gradients); here it is
# folded into the backward's delta term (see _bwd).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               bwd_block_q, bwd_block_k):
    return _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, None)


def _flash_lse_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                   bwd_block_q, bwd_block_k):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                    None)
    # the ring's per-step output is NOT named as _flash_fwd's is: a policy
    # that keeps "attn_out" would then hold one partial output a ring step
    # a layer, n times the merged one the model names (unmeasured: PERF.md
    # §7).  So under every policy the ring's backward runs each step's
    # forward kernel again, and the program is what it was before PR 38
    lse = checkpoint_name(lse, "attn_lse")
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(sm_scale, causal, block_q, block_k, interpret,
                   bwd_block_q, bwd_block_k, res, g):
    do, dlse = g
    return _bwd(sm_scale, causal, bwd_block_q, bwd_block_k, interpret, res,
                do, None, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                    bias=None, block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    bwd_block_q: Optional[int] = None,
                    bwd_block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    block_mask=None, return_lse: bool = False):
    """q [B,S,Hq,hd], k/v [B,S,Hkv,hd] -> [B,S,Hq,hd]
    (or ``(out, lse [B,Hq,S])`` with ``return_lse`` — the ring-attention
    inner block consumes the lse for its cross-block merge).

    bias is not fused (alibi models use the XLA path); causal is.
    ``block_mask`` (optional bool [S/block_q, S/block_k]) skips dead blocks in
    forward AND backward — the block-sparse attention path
    (ops/sparse_attention builds the patterns).
    Backward tiles default to min(fwd tile, 1024): the fwd wins with a
    full-row K block while the bwd kernels prefer (and compile reliably at)
    1024.  A ``block_mask`` forces bwd tiles == fwd tiles (the mask grid must
    match every kernel).
    """
    if bias is not None:
        raise NotImplementedError("bias is handled by the XLA attention path")
    S = q.shape[1]
    if block_mask is not None:
        # masked path: ONE tile size for every kernel (the mask grid must
        # match fwd, dq, and dkv), capped at 1024 — the bwd kernels do not
        # compile reliably above that, so the fwd's full-row preference is
        # forfeited here rather than handed to the backward
        block_q = _pick_block(S, min(block_q, 1024))
        block_k = _pick_block(S, min(block_k, 1024))
        bwd_block_q, bwd_block_k = block_q, block_k
        import numpy as _np

        bm = _np.asarray(block_mask)
        want = (S // block_q, S // block_k)
        if bm.shape != want:
            raise ValueError(
                f"block_mask shape {bm.shape} does not match the block grid "
                f"{want} (S={S}, block_q={block_q}, block_k={block_k})")
        # hashable static arg for the custom_vjp/jit caches
        block_mask = tuple(tuple(int(x) for x in row) for row in bm)
    else:
        block_q = _pick_block(S, block_q)
        block_k = _pick_block(S, block_k)
        bwd_block_q = _pick_block(S, bwd_block_q or min(block_q, DEFAULT_BWD_BLOCK))
        bwd_block_k = _pick_block(S, bwd_block_k or min(block_k, DEFAULT_BWD_BLOCK))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = resolve_interpret(interpret)
    # [B,S,H,hd] -> [B,H,S,hd]
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    if return_lse:
        if block_mask is not None:
            raise NotImplementedError("return_lse + block_mask")
        # the lse-differentiable variant — callers that CONSUME lse (ring
        # merge) would get silently-wrong grads from _flash's dropped
        # cotangent
        out, lse = _flash_lse(qt, kt, vt, sm_scale, causal, block_q,
                              block_k, interpret, bwd_block_q, bwd_block_k)
        return jnp.swapaxes(out, 1, 2), lse.reshape(lse.shape[0],
                                                    lse.shape[1], -1)
    out, _ = _flash(qt, kt, vt, sm_scale, causal, block_q, block_k,
                    interpret, bwd_block_q, bwd_block_k, block_mask)
    return jnp.swapaxes(out, 1, 2)
