"""RETIRED round-5: Pallas flash-decode, kept ONLY so tools/decode_bench.py
can reproduce the A/B that justified deleting it from the product
(tools/artifacts/decode_r5.json: XLA won 21/22 cells; the single pallas
"win" is an XLA jitter outlier).  Not imported by deepspeed_tpu.

Original docstring:

Pallas flash-decode: single-token attention against the KV cache.

TPU-native analogue of the reference's fused decode attention
(``csrc/transformer/inference/csrc/softmax.cu`` ``attn_softmax_context`` —
the KV-cache read half of ``ds_attention.py:279``).  Decode reads the whole
cache once per token, so the op is HBM-bandwidth bound; the kernel streams
K/V blocks through VMEM with an online softmax, so the [Hq, T] score matrix
never exists in HBM and K/V are read exactly once, **in the cache's native
[B, T, Hkv, hd] layout** (an earlier time-major variant transposed the whole
cache each step — the copy cost more than the kernel saved).  GQA contracts
each query-head group against its KV head in-kernel (no materialized
repeat), same convention as flash_attention.py.

Layouts: q [B, Hq, hd] (the one decode token per row), cache k/v
[B, T, Hkv, hd], mask [B, T] bool (True = attendable: the caller folds
validity + slot-order causality into it).  Output [B, Hq, hd].

Dispatch note (models/transformer.py:_attention_cached): at short cache
lengths the whole decode step is weight-read bound and XLA's fused einsum
path is at parity or better; the kernel is engaged for long caches, where
the [Hq, T] score materialization and cache re-reads start to matter.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 512

import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
from deepspeed_tpu.ops.pallas.common import (  # noqa: E402
    NEG_INF, mask_to_i32, parallel_semantics, pick_block, resolve_interpret)

# B is independent; the T sweep carries the online-softmax state.
_COMPILER_PARAMS = parallel_semantics(1, 1)


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale, blocks_t, Hkv, G):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    mask = mask_ref[0, 0] != 0                     # [Tb] (int32 on the wire:
    # bool memref tiling is a Mosaic lowering hazard — same convention as
    # flash_attention.py's _mask_array)
    m_prev = m_scr[...]                            # [Hkv*G, 1]
    # per-KV-head small dots, unrolled (Hkv is 1-16; Pallas TPU wants rank-2
    # dot_general, and the [Tb, hd] K slice is contiguous in the native
    # cache layout)
    m_rows, l_rows, acc_rows = [], [], []
    for h in range(Hkv):
        q = q_ref[0, h]                            # [G, hd]
        k = k_ref[0, :, h]                         # [Tb, hd]
        v = v_ref[0, :, h]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                           # [G, Tb]
        s = jnp.where(mask[None, :], s, NEG_INF)
        mp = m_prev[h * G:(h + 1) * G]
        mc = jnp.max(s, axis=-1, keepdims=True)
        mn = jnp.maximum(mp, mc)
        p = jnp.exp(s - mn)
        alpha = jnp.exp(mp - mn)
        l_rows.append(l_scr[h * G:(h + 1) * G] * alpha
                      + jnp.sum(p, axis=-1, keepdims=True))
        acc_rows.append(acc_scr[h * G:(h + 1) * G] * alpha
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_rows.append(mn)
    m_scr[...] = jnp.concatenate(m_rows, axis=0)
    l_scr[...] = jnp.concatenate(l_rows, axis=0)
    acc_scr[...] = jnp.concatenate(acc_rows, axis=0)

    @pl.when(t == blocks_t - 1)
    def _finish():
        # a fully-masked row (no valid slots at all) divides by 0 — the
        # caller guarantees >=1 attendable slot (the token just written)
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def flash_decode(q: jax.Array, ck: jax.Array, cv: jax.Array, mask: jax.Array,
                 sm_scale: Optional[float] = None,
                 block_t: int = DEFAULT_BLOCK_T,
                 interpret: Optional[bool] = None) -> jax.Array:
    """q [B,Hq,hd] x cache [B,T,Hkv,hd], mask [B,T] -> [B,Hq,hd]."""
    B, Hq, hd = q.shape
    T, Hkv = ck.shape[1], ck.shape[2]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not divisible by Hkv={Hkv}")
    G = Hq // Hkv
    if T % 128:
        raise NotImplementedError(
            f"cache length {T} must be a multiple of 128 (lane-aligned "
            "blocks); use the XLA path")
    bt = pick_block(T, block_t, floor=128)
    blocks_t = T // bt
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    interpret = resolve_interpret(interpret)

    qg = q.reshape(B, Hkv, G, hd)
    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, blocks_t=blocks_t,
                          Hkv=Hkv, G=G),
        grid=(B, blocks_t),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, hd), lambda b, t: (b, 0, 0, 0)),
            pl.BlockSpec((1, bt, Hkv, hd), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, bt, Hkv, hd), lambda b, t: (b, t, 0, 0)),
            # [B, 1, T]: the (sublane, lane) tile is (1, bt) — legal for any
            # B (a [B, T] layout would need the B tile divisible by 8)
            pl.BlockSpec((1, 1, bt), lambda b, t: (b, 0, t)),
        ],
        out_specs=pl.BlockSpec((1, Hq, hd), lambda b, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),      # running max
            pltpu.VMEM((Hq, 1), jnp.float32),      # running sum
            pltpu.VMEM((Hq, hd), jnp.float32),     # output accumulator
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(qg, ck, cv, mask_to_i32(mask[:, None, :]))
    return out
