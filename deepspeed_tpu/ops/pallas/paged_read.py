"""A decode tick's paged read, each live page fetched once (Pallas, TPU).

A tick of one token a slot attends the call's live (slot, page) pairs
(``models/transformer.py`` :func:`_paged_read_plan`).  Written in
``jax.numpy`` (:func:`_attention_paged`) a step of the read first **copies**
its pairs' whole pages out of the pool (``pool[pages]``: XLA cannot fuse a
gather into a product) and then attends the copy, so every live K/V row is
read, written and read again.  Here the page ids are scalar-prefetched and a
K or V block's index map returns the pair's physical page: the pipeline
copies one page's block straight from the pool where it lies into on-chip
memory, the next pairs' while these are attended, and nothing of K/V is
written back.  The grid is as long as the live pairs (its bound is read on
the device): the list's rounding to whole steps costs nothing.

**What a pair computes.**  Its block of the softmax for every query head of
its slot at once, as two plain matrix products over the page block flattened
to ``[Hkv * page, hd]`` (a merge of leading axes, which moves nothing): ``s =
q [Hq, hd] x k^T`` gives every head's query against every KV head's rows, and
a mask keeps a query head's own KV head (its group's) and the rows ``r <=
limit`` of the page; what the mask drops weighs exactly 0.  The products run
at the rate the matrix unit takes K and V in, whatever the waste in
arithmetic: a tick's read is bound by bytes, not by operations.  The same two
products read a leaf the device stores head-major (``[Hkv, page, hd]`` a
page: column ``c`` is head ``c // page``, row ``c % page``) and row-major
(``[page, Hkv, hd]``: head ``c % Hkv``, row ``c // Hkv``).

**Where the softmax lives.**  The running maximum, sum and accumulator of
EVERY slot stay in on-chip memory for the whole call (float32, ``[B, Hq,
..]``: half a megabyte at 32 slots), set to ``(-1e30, 0, 0)`` by the first
grid step; a pair folds its block into its slot's rows, the blockwise softmax
of :func:`_attention_paged` term for term.  The list is slot-major but
nothing here needs it to be.  A slot no pair names comes out ``l == 0, acc ==
0``: the caller's division gives it 0, not NaN.

``pairs`` pairs a grid step (K and V handed in once a pair of the step, each
with its own index map) spread a step's fixed cost (~0.35 us) over several
small pages.  A pair past the live total names the block already resident in
its place, so nothing is fetched for it, and its body is skipped.

**A latent leaf** (:func:`latent_read`: one row ``[c ; k_pe]`` a token, no
head axis, keys and values the same bytes) is the same read with one block a
pair: ``[r + rd, page]`` as the v5e stores it (page rows minor-most), fetched
once for both products.  The absorbed queries meet the whole block as keys
(``s = q [Hq, r + rd] x c``) and the block's first ``r`` rows as values
(``p x c[:r]^T``, a contraction over the lanes of both); the mask is the
page's rows ``<= limit`` alone, and the softmax state is folded by the same
lines.  Two products over one block are twice the matrix unit's intake a
byte fetched, so this read is bound by how well that unit is kept fed, not
by the bytes alone: a step computes every pair's scores first and folds them
in order after, with no branch between (:func:`_latent_kernel`).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import NEG_INF, parallel_semantics, resolve_interpret

# Bytes of one K or V page block ``[Hkv, page, hd]`` under which the read
# keeps the gather: the smallest block read on the chip, where the kernel
# took 0.29 ms against the gather's 0.64 (4 KV heads of 128, a page of 128:
# tools/paged_read_bench.py, PERF.md section 5); nothing smaller was read
MIN_BLOCK_BYTES = 128 << 10
# Bytes of K blocks a grid step takes, in whole pairs: a step costs ~0.35 us
# whatever it moves, which a block of 131 KB (0.16 us of the chip's
# bandwidth) does not hide and four of them do (0.36 -> 0.29 ms); from 512 KB
# up one pair a step read as fast as any more (same table)
STEP_BYTES = 512 << 10
# ... and of a latent leaf's blocks (:func:`latent_read`): its step runs its
# pairs in one straight line, every pair's scores first, and the matrix unit
# is the busier the more of them there are (a 147 KB block, us a pair: 0.288
# at three a step, 0.252 at four, 0.219 at six, 0.212 at eight and at twelve,
# against 0.180 of bandwidth: tools/paged_read_bench.py, PERF.md section 5)
LATENT_STEP_BYTES = 1152 << 10
# on-chip memory a call may take, and what its two large tenants may: K's
# and V's blocks of a step, double-buffered (4 x MAX_BLOCK_BYTES), and every
# slot's softmax state with the queries (RESIDENT_BYTES); the scores and
# their exponentials lie beside them
VMEM_LIMIT_BYTES = 48 << 20
MAX_BLOCK_BYTES = 4 << 20
RESIDENT_BYTES = 16 << 20


def page_block(k_shape: Tuple[int, ...], v_shape: Tuple[int, ...], dtype,
               axes: str) -> Optional[int]:
    """Bytes of the K page block :func:`paged_read` fetches a pair from
    leaves of ``k_shape`` / ``v_shape`` ``[N, *axes]`` (``axes``: the three
    trailing axes as einsum letters, ``"ktd"`` head-major or ``"tkd"``
    row-major), or ``None`` where the tile plan takes no such leaf: bfloat16,
    heads of whole 128 lanes, K and V of one shape up to the head's width,
    a block whose second-minor axis is whole tiles of 16 sublanes (its
    flattening to ``[Hkv * page, hd]`` then moves nothing), and no block of
    more than ``MAX_BLOCK_BYTES``."""
    if (axes not in ("ktd", "tkd") or len(k_shape) != 4 or len(v_shape) != 4
            or tuple(k_shape[:3]) != tuple(v_shape[:3])
            or jnp.dtype(dtype) != jnp.bfloat16
            or k_shape[3] % 128 or v_shape[3] % 128 or k_shape[2] % 16):
        return None
    block = k_shape[1] * k_shape[2] * max(k_shape[3], v_shape[3]) * 2
    return block if block <= MAX_BLOCK_BYTES else None


def resident_bytes(slots: int, heads: int, hd: int, vd: int) -> int:
    """Bytes :func:`paged_read` keeps in on-chip memory for the whole call:
    the queries, and the accumulator and sum (outputs, two buffers each) and
    the maximum of every slot, the heads in whole tiles of 8."""
    rows = -(-heads // 8) * 8
    return slots * rows * (2 * 2 * hd + 4 * (2 * vd + 2 * 128 + 128))


def pairs_a_step(block_bytes: int, step_bytes: int = STEP_BYTES) -> int:
    """Pairs a grid step of :func:`paged_read` takes for a K page block of
    ``block_bytes``: ``step_bytes`` of them, at least one."""
    return max(1, step_bytes // block_bytes)


def _start(i, m_ref, l_ref, acc_ref):
    """The first grid step sets every slot's softmax state."""
    @pl.when(i == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _fold(slot, s, weigh, m_ref, l_ref, acc_ref, keep=None):
    """A pair's masked scores ``s [Hq, cols]`` (float32) folded into its
    slot's running maximum, sum and accumulator; ``weigh(p)`` is the
    exponentials' product with the pair's values ``[Hq, vd]``.  ``keep``
    (the mask again) zeroes what it drops outright, for a pair that may have
    no live column at all (then ``exp(-1e30 - m)`` is 1 while ``m`` is at
    its start)."""
    m_old = m_ref[slot]                     # [Hq, 128], a row one value
    m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    alpha = jnp.exp(m_old - m_new)
    m_ref[slot] = m_new
    l_ref[slot] = l_ref[slot] * alpha + p.sum(-1, keepdims=True)
    pv = weigh(p)
    acc_ref[slot] = acc_ref[slot] * alpha[:, :1] + pv


def _kernel(total_ref, slot_ref, pages_ref, limit_ref, q_ref, *refs,
            pairs: int, head_major: bool, group: int, scale: float):
    del pages_ref                       # the index maps read it
    k_refs, v_refs = refs[:pairs], refs[pairs:2 * pairs]
    state = refs[2 * pairs:][::-1]      # m, l, acc
    i = pl.program_id(0)
    _start(i, *state)

    def fold(at, k_ref, v_ref):
        _, a, b, hd = k_ref.shape
        page, heads = (b, a) if head_major else (a, b)
        slot = slot_ref[at]
        last = jnp.minimum(limit_ref[at], page - 1)     # of the page's rows
        s = jax.lax.dot_general(
            q_ref[slot], k_ref[0].reshape(a * b, hd),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [Hq, a * b]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0) // group
        if head_major:
            ok = (col >= head * page) & (col <= head * page + last)
        else:
            ok = (col % heads == head) & (col < (last + 1) * heads)
        _fold(slot, jnp.where(ok, s, NEG_INF), lambda p: jnp.dot(
            p.astype(v_ref.dtype), v_ref[0].reshape(a * b, v_ref.shape[3]),
            preferred_element_type=jnp.float32), *state)

    for j in range(pairs):
        at = i * pairs + j
        pl.when(at < total_ref[0])(
            functools.partial(fold, at, k_refs[j], v_refs[j]))


def _latent_kernel(total_ref, slot_ref, pages_ref, limit_ref, q_ref, *refs,
                   pairs: int, values: int, scale: float):
    del pages_ref                       # the index maps read it
    c_refs, state = refs[:pairs], refs[pairs:][::-1]     # m, l, acc
    i = pl.program_id(0)
    _start(i, *state)
    # A step's pairs in one straight line, every pair's scores before any
    # fold: products with nothing between them keep the matrix unit fed,
    # where a pair folded under its own ``pl.when`` leaves it waiting on
    # the softmax before the next block goes in (0.45 -> 0.21 us a pair at
    # eight a step: tools/paged_read_bench.py, PERF.md section 5).  So a pair
    # past the total runs too, masked whole: it names a resident block, is
    # no slot's (``slot == B``: the last slot's rows stand in) and folds
    # exactly nothing.
    at = [i * pairs + j for j in range(pairs)]
    slot = [jnp.minimum(slot_ref[a], q_ref.shape[0] - 1) for a in at]
    limit = [jnp.where(a < total_ref[0], limit_ref[a], -1) for a in at]
    scores = [jnp.dot(q_ref[b], c_ref[0],
                      preferred_element_type=jnp.float32) * scale
              for b, c_ref in zip(slot, c_refs)]              # [Hq, page]
    for b, last, s, c_ref in zip(slot, limit, scores, c_refs):
        ok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) <= last
        _fold(b, jnp.where(ok, s, NEG_INF), lambda p: jax.lax.dot_general(
            p.astype(c_ref.dtype), c_ref[0, :values],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), *state, keep=ok)


# jitted, as the other kernels a program holds several times are (ROADMAP
# S12): a trace a shape, not a trace a call site
@functools.partial(jax.jit,
                   static_argnames=("axes", "scale", "pairs", "interpret"))
def paged_read(q, k, v, total, slot, pages, limit, *, axes: str,
               scale: float, pairs: Optional[int] = None,
               interpret: Optional[bool] = None):
    """``q [B, Hq, hd]`` (one token a slot) against the live pages of ``k`` /
    ``v [N, *axes]``: ``(acc [B, Hq, vd], l [B, Hq])`` float32, the softmax's
    weighted sum of V rows and its sum of exponentials a slot and head (the
    output is ``acc / l``; a slot no pair names has ``l == 0`` and ``acc ==
    0``).  ``slot``, ``pages`` (physical, this layer's) and ``limit [P]``
    int32 are the flat lists of (slot, page) pairs and of the last row of
    each page its slot's query may see, of which the first ``total`` (int32
    scalar, on the device) are live; a grid step takes ``pairs`` of them
    (``None``: :func:`pairs_a_step`'s).
    Shapes outside :func:`page_block` raise ``NotImplementedError``: the
    caller keeps the gather."""
    B, Hq, hd = q.shape
    block = page_block(k.shape, v.shape, k.dtype, axes)
    if block is None or q.dtype != k.dtype or v.dtype != k.dtype or (
            resident_bytes(B, Hq, hd, v.shape[3]) > RESIDENT_BYTES):
        raise NotImplementedError(
            f"paged_read has no tile plan for {q.dtype} queries "
            f"{tuple(q.shape)} over {k.dtype} leaves {tuple(k.shape)} / "
            f"{tuple(v.shape)} stored {axes!r}; use the gather")
    head_major = axes == "ktd"
    heads = k.shape[1] if head_major else k.shape[2]
    return _read(
        functools.partial(_kernel, head_major=head_major, group=Hq // heads,
                          scale=scale),
        q, (k, v), total, slot, pages, limit, v.shape[3],
        pairs or pairs_a_step(block), interpret, "paged_read")


def _read(kernel, q, leaves, total, slot, pages, limit, vd: int, pairs: int,
          interpret, name: str):
    """``kernel`` over a grid as long as the live pairs, ``pairs`` a step:
    each of ``leaves [N, ...]`` handed in once a pair of the step with the
    pair's physical page as its block index, the queries and every slot's
    state whole and resident."""
    B, Hq, hd = q.shape
    total = jnp.asarray(total, jnp.int32).reshape(1)
    # the lists in whole steps: the last step's index maps read every place
    slot, pages, limit = (
        jnp.pad(jnp.asarray(a, jnp.int32), (0, -len(a) % pairs))
        for a in (slot, pages, limit))
    P = slot.shape[0]
    # a pair past the total names the page its place in the step held a step
    # before: the block already resident, so nothing is fetched for it
    at = jnp.arange(P, dtype=jnp.int32)
    pages = jnp.where(at < total[0], pages,
                      pages[jnp.maximum(at - pairs, 0)])
    # the query heads in whole tiles of 8 sublanes; a head past the last is
    # in no KV head's group
    rows = -(-Hq // 8) * 8
    q = jnp.pad(q, ((0, 0), (0, rows - Hq), (0, 0)))

    def whole(i, *_):
        return (0, 0, 0)

    def page_of(j, ndim):
        return lambda i, total, slot, pages, limit: (
            pages[i * pairs + j],) + (0,) * (ndim - 1)

    acc, l = pl.pallas_call(
        functools.partial(kernel, pairs=pairs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # as long as the live pairs; one step where there is none, which
            # leaves every slot at its start
            grid=(jnp.maximum((total[0] + pairs - 1) // pairs, 1),),
            in_specs=[pl.BlockSpec((B, rows, hd), whole)]
            + [pl.BlockSpec((1,) + tuple(a.shape[1:]), page_of(j, a.ndim))
               for a in leaves for j in range(pairs)],
            out_specs=[pl.BlockSpec((B, rows, vd), whole),
                       pl.BlockSpec((B, rows, 128), whole)],
            scratch_shapes=[pltpu.VMEM((B, rows, 128), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, rows, vd), jnp.float32),
                   jax.ShapeDtypeStruct((B, rows, 128), jnp.float32)],
        compiler_params=parallel_semantics(
            0, 1, vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret), name=name,
    )(total, slot, pages, limit, q, *(a for a in leaves for _ in range(pairs)))
    return acc[:, :Hq], l[:, :Hq, 0]


def latent_block(shape: Tuple[int, ...], values: int, dtype) -> Optional[int]:
    """Bytes of the page block :func:`latent_read` fetches a pair from a
    latent leaf seen as ``shape [N, r + rd, page]`` whose first ``values``
    rows of a block are its values, or ``None`` where the tile plan takes no
    such leaf: bfloat16, a page of whole 128 lanes, ``r + rd`` whole tiles
    of 16 sublanes, ``values`` of them whole lanes of the accumulator, and no
    block of more than ``MAX_BLOCK_BYTES``."""
    if (len(shape) != 3 or jnp.dtype(dtype) != jnp.bfloat16
            or shape[2] % 128 or shape[1] % 16
            or not 0 < values <= shape[1] or values % 128):
        return None
    block = shape[1] * shape[2] * 2
    return block if block <= MAX_BLOCK_BYTES else None


@functools.partial(jax.jit,
                   static_argnames=("values", "scale", "pairs", "interpret"))
def latent_read(q, c, total, slot, pages, limit, *, values: int,
                scale: float, pairs: Optional[int] = None,
                interpret: Optional[bool] = None):
    """:func:`paged_read` over a latent leaf: the absorbed queries ``q [B,
    Hq, r + rd]`` (one token a slot) against the live pages of ``c [N, r +
    rd, page]``, each row of a page a key whole and a value in its first
    ``values`` (``r``) columns: ``(acc [B, Hq, r], l [B, Hq])`` float32, the
    lists as :func:`paged_read`'s.  Shapes outside :func:`latent_block`
    raise ``NotImplementedError``: the caller keeps the gather."""
    B, Hq, hd = q.shape
    block = latent_block(c.shape, values, c.dtype)
    if block is None or q.dtype != c.dtype or hd != c.shape[1] or (
            resident_bytes(B, Hq, hd, values) > RESIDENT_BYTES):
        raise NotImplementedError(
            f"latent_read has no tile plan for {q.dtype} queries "
            f"{tuple(q.shape)} over a {c.dtype} leaf {tuple(c.shape)} with "
            f"{values} value rows; use the gather")
    return _read(
        functools.partial(_latent_kernel, values=values, scale=scale),
        q, (c,), total, slot, pages, limit, values,
        pairs or pairs_a_step(block, LATENT_STEP_BYTES), interpret,
        "latent_read")
