"""The state-space decode step as ONE pass over the state (Pallas, TPU).

A decode tick of a model with a state a slot (``models/transformer.py``
:func:`_ssm_step`) advances every live slot's state ``S [H, P, N]`` float32 by
one token and reads the token's output from the NEW state:

    S' = exp(dt A) S + (dt x) (x) B            y = S' C

Written in ``jax.numpy`` the compiler makes two ops of it, an in-place update
of the slot rows in the cache leaf (one read and one write of the state) and a
reduction that reads the state again: two and a half passes over the largest
item of a tick (PERF.md, PR 40).  Here the cache leaf itself goes in and comes
out (``input_output_aliases``); a grid step loads one slot's block of ``hb``
heads into on-chip memory, updates it, reduces the block it now holds against
``C`` and stores block and ``y``: one read and one write, whatever else.

The arithmetic is :func:`_ssm_step`'s, term for term: every number float32,
the sum over the state's columns a product and a reduction on the vector unit
(no matrix unit rounds the state to read it), nothing re-associated.  A row
whose ``dt`` is 0 (a masked token) keeps its state; a ``fresh`` row starts
from zeros.  Rows of the leaf the grid does not visit (other layers') are not
touched: the alias is the whole leaf, the blocks are this layer's.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import mask_to_i32, parallel_semantics, resolve_interpret

# Heads a grid step holds: a block is [8, 128, 256] float32 = 1 MiB at the
# published widths, 4 MiB with both directions double-buffered, inside the
# v5e's default scoped on-chip memory.  On the v5e (96 slots x 5 layers of
# 32 x 128 x 256; PERF.md, PR 41) 8, 16 and 32 heads a block all move a
# layer's 805 MB in 1.23 ms, and so does a kernel that only copies the
# blocks: the step runs at the chip's rate for reading and writing at once,
# and the arithmetic below is free under it.
HEAD_BLOCK = 8


def head_block(heads: int, groups: int, head_dim: int,
               state: int) -> Optional[int]:
    """Heads a grid step of :func:`ssm_step` holds for a state ``[heads,
    head_dim, state]`` whose B and C are shared by ``heads // groups`` heads,
    or ``None`` where the tile plan takes no such shape: a block's last two
    axes are whole (8, 128) float32 tiles (the state's ``[head_dim, state]``
    and the token's ``[hb, head_dim]``), and a block lies inside ONE group so
    that it reads one B and one C."""
    if head_dim % 8 or state % 128 or (heads // groups) % HEAD_BLOCK:
        return None
    return HEAD_BLOCK


def _kernel(row0_ref, fresh_ref, decay_ref, state_ref, xd_ref, b_ref, c_ref,
            out_ref, y_ref, *, hb: int, heads: int):
    slot, hk = pl.program_id(0), pl.program_id(1)
    fresh = fresh_ref[slot] != 0
    bv, cv = b_ref[0, 0], c_ref[0, 0]               # [1, N], the group's
    xd = xd_ref[0]                                  # [hb, P]
    ys = []
    for j in range(hb):
        decay = decay_ref[slot * heads + hk * hb + j]
        s = jnp.where(fresh, 0.0, state_ref[0, j])  # [P, N]
        s = s * decay + xd[j][:, None] * bv
        out_ref[0, j] = s
        ys.append((s * cv).sum(-1))
    y_ref[0] = jnp.stack(ys)


def ssm_step(leaf, row0, fresh, decay, xd, Bm, Cm, *,
             interpret: Optional[bool] = None):
    """One token a row for the ``B`` rows ``row0 .. row0 + B - 1`` of the
    cache leaf ``leaf [R, H, P, N]`` float32, in place.

    ``row0``: int32 scalar (traced inside the layer scan: ``layer * slots``);
    ``fresh [B]`` bool: the row starts its sequence, from zeros;
    ``decay [B, H]`` = ``exp(dt A)`` and ``xd [B, H, P]`` = ``dt x``, float32;
    ``Bm``, ``Cm [B, G, N]`` float32.  Returns ``(leaf with the rows
    advanced, y [B, H, P] float32)``.  Shapes outside :func:`head_block`
    raise ``NotImplementedError``: the caller keeps :func:`_ssm_step`."""
    R, H, P, N = leaf.shape
    B, G = Bm.shape[:2]
    hb = head_block(H, G, P, N)
    if hb is None or leaf.dtype != jnp.float32:
        raise NotImplementedError(
            f"ssm_step has no tile plan for a {leaf.dtype} state "
            f"[{H}, {P}, {N}] in {G} group(s); use the XLA path")
    per_group = H // G

    def rows(b, h, row0, fresh, decay):
        return (row0[0] + b, h, 0, 0)

    def group(b, h, row0, fresh, decay):
        return (b, h * hb // per_group, 0, 0)

    def token(b, h, row0, fresh, decay):
        return (b, h, 0)

    return pl.pallas_call(
        functools.partial(_kernel, hb=hb, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, H // hb),
            in_specs=[pl.BlockSpec((1, hb, P, N), rows),
                      pl.BlockSpec((1, hb, P), token),
                      pl.BlockSpec((1, 1, 1, N), group),
                      pl.BlockSpec((1, 1, 1, N), group)],
            out_specs=[pl.BlockSpec((1, hb, P, N), rows),
                       pl.BlockSpec((1, hb, P), token)]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                   jax.ShapeDtypeStruct((B, H, P), jnp.float32)],
        # operand 3 (after the three prefetched scalars) is the leaf
        input_output_aliases={3: 0},
        compiler_params=parallel_semantics(2, 0),
        interpret=resolve_interpret(interpret), name="ssm_step",
    )(jnp.asarray(row0, jnp.int32).reshape(1), mask_to_i32(fresh),
      decay.reshape(-1), leaf, xd, Bm.reshape(B, G, 1, N),
      Cm.reshape(B, G, 1, N))
