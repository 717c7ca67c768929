"""The sorted rows' way in and way back, over the live rows alone.

``moe_ffn_nodrop`` (``moe/sharded_moe.py``) sorts a call's (token, expert)
rows by expert; the rows of some group here lie first, ``n_live`` of them
(the group sizes' sum, known on the device), the rows of no group (another
chip's experts, a prompt's padding) after them.  Where the grouped products
run as ``ops/pallas/grouped_matmul.py`` those dead rows are never computed,
so moving them is all they cost: half of a Granite chunk's 20,480 rows of
4,096, seven eighths of Kanana's, a padding chunk's every one (PERF.md,
PR 50).  Here a row that is in no group is never moved, and a live row moves
once each way, as it is stored:

- :func:`rows_in`: ``xs[r] = x[tok[r]]`` for the sorted rows under ``n_live``,
  a tile of :data:`ROWS_IN_TILE` rows a step of a loop whose trip count is
  read from ``n_live``.  **The rows past the last live tile are not written**:
  they hold whatever that memory held, as the kernel's outputs do, and
  nothing downstream reads them.
- :func:`rows_back`: a token's result is the float32 sum of ``gate x row``
  over its pairs whose expert is held, each such row of ``out`` read once in
  the dtype it was stored in, a tile of :data:`TOKENS_BACK_TILE` tokens a
  step up to the last real token.  A pair in no group is not multiplied by
  anything: its index is turned to row 0 and what comes back is dropped by a
  select (never ``0 x`` unwritten memory).  The tokens past the last real
  one come out 0.

Both are plain XLA under ``lax.fori_loop``: Mosaic takes no copy of one row
out of a tiled ``[rows, D]`` array ("slice shape along dimension 0 must be
aligned to tiling (8)"), so a Pallas gather by row DMA is not to be had
(PERF.md section 5, PR 50).  A loop with a traced trip count has no reverse
rule; under differentiation the forward is this one and the backward that of
the plain form (one tile over every row), as ``grouped_matmul`` does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..ops.pallas.common import resolve_interpret

# Sorted rows a step of the way in: the rows moved are the live ones rounded
# up to whole tiles, so a tile is small beside a chunk's live rows (2,500 and
# over where the kernel runs at all) and large beside a loop step's cost
ROWS_IN_TILE = 512
# Tokens a step of the way back (x top_k rows gathered a step)
TOKENS_BACK_TILE = 256


def moved_rows(n_live, rows: int):
    """Rows :func:`rows_in` fills for ``n_live`` live rows of ``rows``
    sorted ones: ``n_live`` in whole tiles (host or device arithmetic)."""
    tile = min(ROWS_IN_TILE, rows)
    return pl.cdiv(n_live, tile) * tile


def _unwritten(shape, dtype, interpret):
    """A buffer nobody has written: a kernel with no body owns its output,
    and XLA has no other way to say "allocate, do not fill" (``jnp.empty``
    is a fill of zeros: 168 MB a Granite chunk)."""
    return pl.pallas_call(
        lambda out_ref: None,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=resolve_interpret(interpret), name="unwritten_rows")()


def _fill(x, tok, n_live, interpret):
    rows, tile = tok.shape[0], min(ROWS_IN_TILE, tok.shape[0])

    def step(i, xs):
        # the last tile of a row count that is no whole number of tiles
        # starts early and rewrites what the one before it wrote
        at = jnp.minimum(i * tile, rows - tile)
        return jax.lax.dynamic_update_slice(
            xs, x[jax.lax.dynamic_slice(tok, (at,), (tile,))], (at, 0))

    return jax.lax.fori_loop(
        0, pl.cdiv(n_live, tile), step,
        _unwritten((rows, x.shape[1]), x.dtype, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_in(x, tok, n_live, interpret):
    return _fill(x, tok, n_live, interpret)


def _rows_in_fwd(x, tok, n_live, interpret):
    xs = _fill(x, tok, n_live, interpret)
    # what the plain gather hands a training step is finite everywhere:
    # nothing read from unwritten memory meets a gradient
    dead = jnp.arange(tok.shape[0])[:, None] >= n_live
    return jnp.where(dead, 0, xs), (x, tok)


def _rows_in_bwd(interpret, res, ct):
    x, tok = res
    _, vjp = jax.vjp(lambda a: a[tok], x)
    return (*vjp(ct), None, None)


_rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


def _sum_tile(out, inv, gates, n_live):
    """``[t, D]`` float32: Σ over a token's held pairs of gate x row, for
    the ``t`` tokens whose pairs' sorted rows are ``inv [t, k]``.  The rows
    are fetched a pair slot at a time, ``[k, t, D]``: ``t`` rows are whole
    tiles of the device's layout where ``k`` (6, 8, 10) is not, so the sum
    over the slots is an add of whole ``[t, D]`` planes and no copy re-lays
    the fetched rows out (``[t, k, D]`` cost a prompt 5 ms of copies and a
    third of the sum's time: PERF.md section 5, PR 50)."""
    held = (inv < n_live).T
    got = out[jnp.where(held, inv.T, 0).reshape(-1)]
    got = got.reshape(*held.shape, -1).astype(jnp.float32)
    return jnp.sum(jnp.where(held[:, :, None], got, 0)
                   * gates.T[:, :, None], axis=0)


def _sum(out, inv, gates, n_live, n_tokens):
    T, tile = inv.shape[0], min(TOKENS_BACK_TILE, inv.shape[0])

    def step(i, y):
        at = jnp.minimum(i * tile, T - tile)
        part = _sum_tile(
            out, jax.lax.dynamic_slice(inv, (at, 0), (tile, inv.shape[1])),
            jax.lax.dynamic_slice(gates, (at, 0), (tile, gates.shape[1])),
            n_live)
        return jax.lax.dynamic_update_slice(y, part.astype(y.dtype), (at, 0))

    return jax.lax.fori_loop(0, pl.cdiv(n_tokens, tile), step,
                             jnp.zeros((T, out.shape[1]), out.dtype))


@jax.custom_vjp
def _rows_back(out, inv, gates, n_live, n_tokens):
    return _sum(out, inv, gates, n_live, n_tokens)


def _rows_back_fwd(out, inv, gates, n_live, n_tokens):
    return (_sum(out, inv, gates, n_live, n_tokens),
            (out, inv, gates, n_live, n_tokens))


def _rows_back_bwd(res, ct):
    out, inv, gates, n_live, n_tokens = res
    real = jnp.arange(inv.shape[0])[:, None] < n_tokens
    _, vjp = jax.vjp(
        lambda o, g: jnp.where(real, _sum_tile(o, inv, g, n_live), 0
                               ).astype(o.dtype), out, gates)
    d_out, d_gates = vjp(ct)
    return d_out, None, d_gates, None, None


_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)


# jitted, as ``grouped_matmul`` is: a trace a shape, not a trace a call
@functools.partial(jax.jit, static_argnames=("interpret",))
def rows_in(x, tok, n_live, interpret=None):
    """``xs [rows, D]`` with ``xs[r] = x[tok[r]]`` for ``r`` under ``n_live``
    rounded up to a whole tile (:func:`moved_rows`), for ``x [T, D]``, ``tok
    [rows]`` int32 and ``n_live`` a traced scalar.  **The rows past them are
    not written.**  ``interpret``: the flag of the kernel that owns the
    output (``ops/pallas/common.py``)."""
    return _rows_in(x, tok, n_live, interpret)


@jax.jit
def rows_back(out, inv, gates, n_live, n_tokens):
    """``y [T, D]`` in ``out``'s dtype: for each token under ``n_tokens``
    the float32 sum over its ``k`` pairs of ``gates[t, j] x out[inv[t, j]]``
    where ``inv[t, j] < n_live``, a pair elsewhere adding exactly nothing
    whatever ``out`` holds past ``n_live``; 0 for the tokens from
    ``n_tokens`` on.  ``out [rows, D]``, ``inv [T, k]`` int32 (the sorted
    row of each pair), ``gates [T, k]`` float32, the two counts traced
    scalars."""
    return _rows_back(out, inv, gates, n_live, n_tokens)
