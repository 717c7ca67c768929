"""The gated-delta-rule decode step as ONE pass over the state (Pallas, TPU).

A decode tick of a model with delta layers (``models/transformer.py``
:func:`_delta_step`) advances every live slot's matrix state ``S [dk, dv]``
float32 a head by one token, and reads the state TWICE by its equations: the
correction reads the decayed state, the output the new one:

    S <- a S          u = S^T k          S <- S + k (b (v - u))^T          o = S^T q

Written in ``jax.numpy`` that is an update in place and two reductions that
each read the state again.  Here the cache leaf itself goes in and comes out
(``input_output_aliases``); a grid step loads one slot's block of heads into
on-chip memory, and both reads, the decay and the update are of that block:
one read and one write of a live slot's state a tick, whatever else.

**How the leaf is laid out.**  A head's state is ``[96, 192]`` at the
published widths.  Kept ``[.., heads, 96, 192]`` each row of 192 floats pads
to 256 lanes in memory and on the way through: +33% bytes a tick.  Kept
``[.., heads, 18,432]`` nothing pads, but a row of the matrix then straddles
the 128-lane tiles and neither product is a plain reduction.  The leaf is
kept ``[.., heads / 2, 96, 384]``: TWO heads' value columns side by side in
one row (``models.mixers.delta.delta_pack``), 384 = 3 x 128 lanes, nothing
padded, and every operation below is a row-wise one over both heads at once
(each head's key is laid over its own 192 lanes by a select).  This packed
form is the one measured on the chip (PERF.md, PR 51); the padded and the
flat forms were not built.

The arithmetic is :func:`_delta_step`'s, term for term: every number
float32, the two reads products and reductions over the key axis on the
vector unit (no matrix unit rounds the state to read it).  A row whose ``a``
is 1 and ``b`` 0 (a masked token) keeps its state; a ``fresh`` row starts
from zeros.  Rows of the leaf the grid does not visit (other layers') are
not touched: the alias is the whole leaf, the blocks are this layer's.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import mask_to_i32, parallel_semantics, resolve_interpret

# Bytes of state a grid step holds: with both directions double-buffered
# four such blocks lie in on-chip memory, inside the v5e's default scoped 16
# MiB beside the step's temporaries
BLOCK_BYTES = 1 << 20


def head_block(rows: int, key_dim: int, width: int) -> Optional[int]:
    """Rows of the packed state ``[rows, key_dim, width]`` (a row = ``pack``
    heads side by side) a grid step of :func:`delta_step` holds: the largest
    divisor of ``rows`` whose block is at most ``BLOCK_BYTES``, or ``None``
    where the tile plan takes no such shape (the block's last two axes are
    whole (8, 128) float32 tiles)."""
    if key_dim % 8 or width % 128:
        return None
    fit = [n for n in range(1, rows + 1)
           if rows % n == 0 and n * key_dim * width * 4 <= BLOCK_BYTES]
    return max(fit) if fit else None


def _kernel(row0_ref, fresh_ref, a_ref, b_ref, state_ref, q_ref, k_ref, v_ref,
            out_ref, o_ref, *, hb: int, pack: int, heads: int):
    slot, blk = pl.program_id(0), pl.program_id(1)
    fresh = fresh_ref[slot] != 0
    width = state_ref.shape[-1]
    # which of a row's ``pack`` heads a lane belongs to
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // (
        width // pack)

    def spread(values):
        """One value a head of the row -> ``[.., width]`` over its lanes."""
        out = values[0]
        for i in range(1, pack):
            out = jnp.where(lane_head == i, values[i], out)
        return out

    for j in range(hb):
        first = (blk * hb + j) * pack           # the row's first head
        at = slot * heads + first
        a = spread([a_ref[at + i] for i in range(pack)])        # [1, width]
        b = spread([b_ref[at + i] for i in range(pack)])
        kk = spread([k_ref[0, 0, j * pack + i][:, None]
                     for i in range(pack)])                     # [dk, width]
        qq = spread([q_ref[0, 0, j * pack + i][:, None]
                     for i in range(pack)])
        s = jnp.where(fresh, 0.0, state_ref[0, j]) * a
        u = (s * kk).sum(0, keepdims=True)
        s = s + kk * (b * (v_ref[0, 0, j][None, :] - u))
        out_ref[0, j] = s
        o_ref[0, 0, j] = (s * qq).sum(0)


def delta_step(leaf, row0, fresh, a, b, q, k, v, *,
               interpret: Optional[bool] = None):
    """One token a row for the ``B`` rows ``row0 .. row0 + B - 1`` of the
    cache leaf ``leaf [R, H / p, dk, p * dv]`` float32, in place.

    ``row0``: int32 scalar (``layer * slots``); ``fresh [B]`` bool: the row
    starts its sequence, from zeros; ``a [B, H]`` the decay ``exp(g)`` and
    ``b [B, H]`` the write strength, float32; ``q``, ``k [B, H, dk]`` and ``v
    [B, H, dv]`` float32 (q and k L2-normed, q scaled).  Returns ``(leaf
    with the rows advanced, o [B, H, dv] float32)``.  Shapes outside
    :func:`head_block` raise ``NotImplementedError``: the caller keeps
    :func:`_delta_step`."""
    R, rows, dk, width = leaf.shape
    B, H = a.shape
    pack = H // rows
    hb = head_block(rows, dk, width)
    if hb is None or leaf.dtype != jnp.float32 or rows * pack != H:
        raise NotImplementedError(
            f"delta_step has no tile plan for a {leaf.dtype} state "
            f"[{rows}, {dk}, {width}] of {H} heads; use the plain step")
    nb = rows // hb

    def state_rows(s, h, row0, fresh, a, b):
        return (row0[0] + s, h, 0, 0)

    def token(s, h, row0, fresh, a, b):
        return (s, h, 0, 0)

    leaf, o = pl.pallas_call(
        functools.partial(_kernel, hb=hb, pack=pack, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, nb),
            in_specs=[pl.BlockSpec((1, hb, dk, width), state_rows),
                      pl.BlockSpec((1, 1, hb * pack, dk), token),
                      pl.BlockSpec((1, 1, hb * pack, dk), token),
                      pl.BlockSpec((1, 1, hb, width), token)],
            out_specs=[pl.BlockSpec((1, hb, dk, width), state_rows),
                       pl.BlockSpec((1, 1, hb, width), token)]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                   jax.ShapeDtypeStruct((B, nb, hb, width), jnp.float32)],
        # operand 4 (after the four prefetched scalars) is the leaf
        input_output_aliases={4: 0},
        compiler_params=parallel_semantics(2, 0),
        interpret=resolve_interpret(interpret), name="delta_step",
    )(jnp.asarray(row0, jnp.int32).reshape(1), mask_to_i32(fresh),
      a.reshape(-1), b.reshape(-1), leaf,
      q.reshape(B, nb, hb * pack, dk), k.reshape(B, nb, hb * pack, dk),
      v.reshape(B, nb, hb, width))
    return leaf, o.reshape(B, H, width // pack)
