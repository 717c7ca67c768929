"""A grouped matrix product over the held groups' row tiles (Pallas, TPU).

A dropless expert layer (``moe/sharded_moe.py`` :func:`moe_ffn_nodrop`) sorts
its (token, expert) rows by expert and multiplies each expert's rows by that
expert's matrix: ``out[rows of group g] = lhs[rows of group g] @ rhs[g]``,
the groups laid end to end from row 0, ``group_sizes[g]`` rows each, the rows
of no group (another chip's experts, a prompt's padding) past the last one.
Written as ``lax.ragged_dot`` the TPU compiler makes one Mosaic call of it, in
tiles of its own choosing, that streams each group's matrix at a quarter to a
third of the chip's bandwidth whatever the rows: 1.6 ms on the v5e for a
prompt's chunk of 20,480 rows over 36 groups of 4,096 x 768, 1.2 ms with an
eighth of the rows live (PERF.md, PR 48).

Here the grid is ``(n tiles, visits, k tiles)`` and a *visit* is one (row
tile, group) pair that share a row, counted from the group sizes on the
device as ``jax.experimental.pallas.ops.tpu.megablox`` counts them: a row
tile with no row of any group is never visited, an empty group (a stack's
other layers) has no visit, a tile that two groups share is visited once for
each and each visit stores its own rows.  ``k`` is whole in a tile wherever a
group's ``[k, tn]`` slice fits on-chip memory, so a group's matrix is read
from device memory once, while its first row tile is computed, and stays for
its other tiles.  bfloat16 operands, float32 sums, the caller's dtype out:
``lax.ragged_dot``'s arithmetic.

**Rows of no group are not computed and not stored: their rows of the output
hold whatever that memory held.**  ``moe_ffn_nodrop`` never reads them: its
way back (``moe/live_rows.py`` :func:`rows_back`) fetches the rows under the
group sizes' sum alone, its way in fills no others, and ``silu(gate) * up``
of such rows feeds only rows that the down product does not visit either.
Under differentiation the forward zeroes them itself and the backward is
``lax.ragged_dot``'s own, so a training step computes what it computed.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import parallel_semantics, resolve_interpret

# On-chip memory the call may use (the v5e holds 128 MiB; the compiler's own
# scoped default, 16 MiB, is less than two buffers of a 4,096 x 768 group)
VMEM_LIMIT_BYTES = 96 * 2 ** 20
# The largest [tk, tn] slice of a group's matrix a grid step holds (twice:
# the next group's arrives while this one is multiplied)
RHS_TILE_BYTES = 8 * 2 ** 20
# Rows of a tile: the matrix unit's height.  A group of r rows touches
# r / ROW_TILE + 1 tiles, so taller tiles compute more rows of other groups
# (284-row groups: 1.45 x their rows at 128, 1.9 x at 256; PERF.md, PR 48)
ROW_TILE = 128


def tiles(m: int, k: int, n: int,
          itemsize: int = 2) -> Optional[Tuple[int, int, int]]:
    """``(tm, tk, tn)`` for ``[m, k] x [G, k, n]``, or ``None`` where
    the plan takes no such shape (the caller keeps ``lax.ragged_dot``): rows
    in whole tiles of :data:`ROW_TILE`, ``k`` and ``n`` whole numbers of 128
    lanes.  ``tk = k`` and ``tn`` the widest divisor of ``n`` whose ``[k,
    tn]`` slice is inside :data:`RHS_TILE_BYTES` (a group's matrix read once
    and the rows once an n tile); a ``k`` too deep for that at 128 columns is
    halved until it fits.  The number of groups does not move the tiles: an
    empty group costs nothing."""
    if m % ROW_TILE or k % 128 or n % 128:
        return None
    tk = k
    while tk * 128 * itemsize > RHS_TILE_BYTES and tk % 256 == 0:
        tk //= 2
    tn = max(t for t in range(128, n + 1, 128)
             if n % t == 0 and (tk * t * itemsize <= RHS_TILE_BYTES
                                or t == 128))
    return ROW_TILE, tk, tn


def group_metadata(group_sizes, m: int, tm: int):
    """``(group_offsets [G+1], group_ids [V], tile_ids [V], visits)`` for
    groups laid end to end from row 0 of ``m`` rows in tiles of ``tm``: visit
    ``v < visits`` multiplies row tile ``tile_ids[v]`` by group
    ``group_ids[v]``, the visits in the order of the rows, so a tile's visits
    are consecutive.  ``V = m // tm + G - 1`` bounds ``visits`` (every tile
    once and every group's first tile once more); the entries past
    ``visits`` are never read by the grid."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    per_group = jnp.where(group_sizes == 0, 0, (ends + tm - 1) // tm - first)
    upto = jnp.cumsum(per_group)
    V = m // tm + G - 1
    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), per_group,
                           total_repeat_length=V)
    tile_ids = (first[group_ids] + jnp.arange(V, dtype=jnp.int32)
                - (upto - per_group)[group_ids])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group_ids,
            jnp.clip(tile_ids, 0, m // tm - 1).astype(jnp.int32),
            upto[-1].astype(jnp.int32))


def _kernel(offsets_ref, groups_ref, tiles_ref, lhs_ref, rhs_ref, out_ref,
            *acc, tm: int, tiles_k: int):
    v, ki = pl.program_id(1), pl.program_id(2)

    def store(sums):
        # this visit's rows of the tile: the group's, no other's
        g = groups_ref[v]
        row = tiles_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        out_ref[...] = jnp.where(mine, sums.astype(out_ref.dtype),
                                 out_ref[...])

    part = jnp.dot(lhs_ref[...], rhs_ref[...],
                   preferred_element_type=jnp.float32)
    if tiles_k == 1:
        store(part)
        return
    acc_ref, = acc

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = part

    @pl.when(ki > 0)
    def _():
        acc_ref[...] += part

    @pl.when(ki == tiles_k - 1)
    def _():
        store(acc_ref[...])


def _call(lhs, rhs, group_sizes, tiling, interpret):
    m, k = lhs.shape
    G, k2, n = rhs.shape
    if k2 != k or group_sizes.shape != (G,):
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape}, rhs {rhs.shape} and "
            f"group_sizes {group_sizes.shape} do not agree")
    tiling = tiling or tiles(m, k, n, lhs.dtype.itemsize)
    if tiling is None or m % tiling[0] or k % tiling[1] or n % tiling[2]:
        raise NotImplementedError(
            f"grouped_matmul: [{m}, {k}] x [{G}, {k}, {n}] is not whole "
            f"tiles of (tm, tk, tn) = {tiling or (ROW_TILE, 128, 128)}: m "
            f"must be a multiple of the row tile "
            f"({(tiling or (ROW_TILE,))[0]}), k and n of theirs; use "
            "lax.ragged_dot")
    tm, tk, tn = tiling
    tiles_k = k // tk
    offsets, group_ids, tile_ids, visits = group_metadata(
        group_sizes.astype(jnp.int32), m, tm)

    def lhs_at(ni, v, ki, offsets, groups, tiles):
        return tiles[v], ki

    def rhs_at(ni, v, ki, offsets, groups, tiles):
        return groups[v], ki, ni

    def out_at(ni, v, ki, offsets, groups, tiles):
        return tiles[v], ni

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, visits, tiles_k),
            in_specs=[pl.BlockSpec((tm, tk), lhs_at),
                      pl.BlockSpec((None, tk, tn), rhs_at)],
            out_specs=pl.BlockSpec((tm, tn), out_at),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if tiles_k > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        # a tile's visits follow one another and each keeps the others'
        # rows: only the n tiles are independent
        compiler_params=parallel_semantics(
            1, 2, vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * (
                m * k * (n // tn) + G * k * n + m * n)),
        interpret=resolve_interpret(interpret), name="grouped_matmul",
    )(offsets, group_ids, tile_ids, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _product(lhs, rhs, group_sizes, tiling, interpret):
    return _call(lhs, rhs, group_sizes, tiling, interpret)


def _fwd(lhs, rhs, group_sizes, tiling, interpret):
    out = _call(lhs, rhs, group_sizes, tiling, interpret)
    # what lax.ragged_dot hands a training step: zeros in the rows of no
    # group, so that nothing read from unwritten memory meets a gradient
    dead = jnp.arange(lhs.shape[0])[:, None] >= group_sizes.sum()
    return jnp.where(dead, 0, out), (lhs, rhs, group_sizes)


def _bwd(tiling, interpret, res, ct):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, group_sizes),
                     lhs, rhs)
    return (*vjp(ct), None)


_product.defvjp(_fwd, _bwd)


# jitted: a program holds the product three times a layer, and a trace a
# shape (not a trace a call: ~30 ms each on the host, twice a program with
# the catalog's own lowering) keeps a serving engine's set-up the parent's
@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def grouped_matmul(lhs, rhs, group_sizes,
                   tiling: Optional[Tuple[int, int, int]] = None,
                   interpret: Optional[bool] = None):
    """``out [m, n]`` with ``out[rows of group g] = lhs[rows of group g] @
    rhs[g]`` for ``lhs [m, k]``, ``rhs [G, k, n]`` of one dtype and
    ``group_sizes [G]`` int32, the groups end to end from row 0; float32
    sums, ``lhs``'s dtype out.  **The rows past the last group are not
    written**: they hold whatever that memory held (see the module's
    docstring).  ``tiling``: ``(tm, tk, tn)``, from :func:`tiles` when not
    given.  A shape that is not whole tiles raises ``NotImplementedError``
    and names the row tile: the caller keeps ``lax.ragged_dot``."""
    return _product(lhs, rhs, group_sizes, tiling, interpret)
