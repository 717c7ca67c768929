"""A decode token's K/V row stored into its page, in place (Pallas, TPU).

A block of ONE token a slot lays one row a slot into a pool leaf ``[N, page,
Hkv, hd]``: slot ``b``'s ``new[b] [Hkv, hd]`` at ``(pages[b], rows[b])``.
Written in ``jax.numpy`` with the pool's other ops (``models/transformer.py``
:func:`_merge_pages`) that is a gather of each slot's whole page, a select of
the one new row over it and a scatter of the whole page back: 2 x ``page``
rows moved a slot where one is new, because a row-granular XLA scatter makes
the TPU compiler re-lay the whole pool out around the layer scan (PERF.md,
PR 25).  A Pallas call fixes its operands' layout instead.  Here the leaf
itself goes in and comes out (``input_output_aliases``) and stays where it
lies in device memory; the kernel starts one copy a slot from ``new`` to the
row's place and waits for them all.  Nothing else of the leaf is read or
written, and nothing passes through on-chip memory.

That holds for a leaf the device stores row-major: its row is then ``Hkv``
whole tiles of lanes, contiguous.  A leaf stored with the page rows
minor-most (a 64-wide or 192-wide head on the v5e) would be copied whole into
row-major order in front of the call and back behind it, so the caller asks
the stored order first (``models.transformer.kv_write_path``).

A slot with no row to keep (masked, idle, past its page table) comes with
``rows[b] < 0`` (and the trash page 0 as its page, as the page merge's plan
sends it) and starts no copy: the program's shape is static, and the leaf
comes out as the page merge leaves it, the trash page included, bit for bit.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import resolve_interpret


def row_block(shape: Tuple[int, ...], dtype) -> Optional[Tuple[int, int]]:
    """The ``(Hkv, hd)`` row :func:`kv_row_write` stores a slot into a leaf
    of ``shape`` and ``dtype``, or ``None`` where the tile plan takes no such
    leaf.  It takes what Mosaic compiles for the v5e, shape by shape
    (``tests/unit/test_chip_bringup.py``): four axes ``[N, page, Hkv, hd]``,
    bfloat16 or float32, ``hd`` a whole number of 128 lanes, and ``Hkv`` 2, 4
    or a whole number of 8 (a row is then whole tiles of ``min(Hkv, 8)``
    sublanes; Mosaic refuses to slice a row of 1, 3 or 12 heads out of its
    tile, and float16 altogether).  An int8 pool's rows and its ``[N, page]``
    scale planes, a latent leaf with no head axis and a head that is not
    whole lanes keep the page merge with them."""
    if (len(shape) != 4 or shape[3] % 128
            or not (shape[2] in (2, 4) or shape[2] % 8 == 0 < shape[2])
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32)):
        return None
    return tuple(shape[2:])


def _kernel(pages_ref, rows_ref, new_ref, leaf_ref, out_ref, sem):
    del leaf_ref                        # the same memory as ``out_ref``

    def row(b):
        return pltpu.make_async_copy(
            new_ref.at[b],
            out_ref.at[pages_ref[b], jnp.maximum(rows_ref[b], 0)], sem)

    def start(b, carry):
        pl.when(rows_ref[b] >= 0)(row(b).start)
        return carry

    def wait(b, carry):
        pl.when(rows_ref[b] >= 0)(row(b).wait)
        return carry

    slots = new_ref.shape[0]
    jax.lax.fori_loop(0, slots, start, 0)
    jax.lax.fori_loop(0, slots, wait, 0)


def kv_row_write(leaf, new, pages, rows, *,
                 interpret: Optional[bool] = None):
    """``leaf [N, page, Hkv, hd]`` with ``new[b] [Hkv, hd]`` stored at
    ``(pages[b], rows[b])`` for each of the ``B`` slots whose ``rows[b] >=
    0``, in place; ``new`` in the leaf's dtype, ``pages`` and ``rows [B]``
    int32.  Shapes outside
    :func:`row_block` raise ``NotImplementedError``: the caller keeps
    :func:`_merge_pages`."""
    if row_block(leaf.shape, leaf.dtype) is None or new.dtype != leaf.dtype:
        raise NotImplementedError(
            f"kv_row_write has no tile plan for a {new.dtype} row into a "
            f"{leaf.dtype} leaf {tuple(leaf.shape)}; use the page merge")
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[where_it_lies, where_it_lies],
            out_specs=where_it_lies,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
        # operand 3 (after the two prefetched scalars and ``new``) is the leaf
        input_output_aliases={3: 0},
        interpret=resolve_interpret(interpret), name="kv_row_write",
    )(jnp.asarray(pages, jnp.int32), jnp.asarray(rows, jnp.int32), new, leaf)
