"""The selective state update over a prompt's block as ONE kernel (Pallas,
TPU): the chunked (SSD) form of ``models/mixers/ssm.py`` :func:`_ssm_scan`
with everything a chunk makes held in on-chip memory.

    S_t = exp(dt_t A) S_t-1 + dt_t x_t (x) B_t        y_t = S_t C_t

Written in ``jax.numpy`` the decays between every two positions of a chunk
are a ``[chunk, chunk, heads]`` float32 tensor in device memory (268 MB for
one 2,048-token piece of a Granite 4.0-H layer), the masked product is
rounded into another, ``y`` and ``x dt`` are copied between ``[position,
head, dim]`` and the head-major order the batched products want, and the
state walks the chunks through a ``lax.scan`` that stacks it.  Here a grid
step is one (row, block of heads inside ONE group, chunk): it loads the
chunk's ``x``, ``dt``, the cumulative sum of ``dt A`` and the group's ``B``
and ``C``, forms ``C B^T`` once, and for each head the decays under the
causal mask, the in-chunk product, the carried state read by ``C``, and what
the chunk adds to the state.  The chunk axis is the grid's last and runs in
order; the carried state ``[hb * P, N]`` float32 lives in scratch, loaded
from the incoming state at chunk 0 and written out once after the last.
``x`` goes in and ``y`` comes out as ``[B, S, H * P]``, the order the mixer
holds them in: what the program reads and writes is ``x``, ``B``, ``C``,
``dt`` and ``y``, once.

The arithmetic is :func:`_ssm_scan`'s, term for term: decays, cumulative sums
and the carried state float32; the four products take operands in the
compute dtype exactly where :func:`_ssm_scan` rounds them (the masked decays
times ``C B^T``, ``x dt``, ``x dt`` decayed to the chunk's end, the state a
chunk starts from) and accumulate in float32.  ``dt = 0`` at a masked or
padded position leaves the state as it was and adds nothing.

Heads narrower than a lane tile (Granite's 64) are taken ``128 // P`` at a
time: a product over the pair's 128 lanes costs the matrix unit what one
head's 64 would, and each head's half is kept by a lane mask, so no slice
cuts a tile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import parallel_semantics, resolve_interpret

# Heads a grid step holds.  At the published widths a step's blocks are
# x [256, 512] bf16 + y [256, 512] f32 + the state [512, 128] f32 three
# times (Granite) or x [128, 1024] + y [128, 1024] + the state [1024, 256]
# three times (Falcon-H1): 2 and 7 MB with both directions double-buffered,
# inside the v5e's default scoped on-chip memory.
HEAD_BLOCK = 8
LANES = 128
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def scan_block(heads: int, groups: int, head_dim: int, state: int,
               chunk: int) -> Optional[int]:
    """Heads a grid step of :func:`ssm_scan` holds for ``heads`` heads of
    ``head_dim`` in ``groups`` groups over a state of ``state`` columns in
    chunks of ``chunk`` positions, or ``None`` where the tile plan takes no
    such shape: the chunk and the state are whole lane tiles (``C B^T`` is
    ``[chunk, chunk]``, ``B`` and ``C`` ``[chunk, state]``), a head is a
    whole lane tile or a whole fraction of one (64: two heads a tile), and
    a block of heads lies inside ONE group and spans whole tiles."""
    P = head_dim
    if (heads % groups or chunk % LANES or state % LANES
            or not (P % LANES == 0 or (P >= 8 and LANES % P == 0))):
        return None
    per_group, per_tile = heads // groups, max(LANES // P, 1)
    for hb in range(min(HEAD_BLOCK, per_group), 0, -1):
        if per_group % hb == 0 and hb % per_tile == 0:
            return hb
    return None


def _kernel(over_ref, x_ref, dt_ref, cum_ref, cumt_ref, b_ref, c_ref, s0_ref,
            y_ref, s_ref, acc, *, hb: int, P: int):
    f32, cd = jnp.float32, x_ref.dtype
    Q = x_ref.shape[1]
    r = max(LANES // P, 1)              # heads a lane tile
    W = r * P                           # lanes they take
    chunk, nc = pl.program_id(2), pl.num_programs(2)
    # this step's heads in over_ref [B * nc * H]
    head0 = ((pl.program_id(0) * nc + chunk) * pl.num_programs(1)
             + pl.program_id(1)) * hb

    @pl.when(chunk == 0)
    def _():
        acc[...] = s0_ref[0]

    Bc, Cc = b_ref[0], c_ref[0]                         # [Q, N], the group's
    cb = jax.lax.dot_general(Cc, Bc, _NT, preferred_element_type=f32)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, W), 1)
    dt, cum, cumt = dt_ref[0, 0], cum_ref[0, 0], cumt_ref[0, 0]
    last = cum[Q - 1:Q]                                 # [1, hb]
    to_end, at = jnp.exp(last - cum), jnp.exp(cum)

    def wide(a, j0):
        """Columns ``j0 .. j0 + r - 1`` of ``a [Q, hb]``, each over its
        head's ``P`` lanes: ``[Q, W]``."""
        out = a[:, j0 + r - 1:j0 + r]
        for q in range(r - 2, -1, -1):
            out = jnp.where(lane < (q + 1) * P, a[:, j0 + q:j0 + q + 1], out)
        return out

    for t in range(hb // r):
        j0, lanes = t * r, slice(t * W, (t + 1) * W)
        xd = x_ref[0, :, lanes].astype(f32) * wide(dt, j0)
        xdc = xd.astype(cd)
        y = None
        for q in range(r - 1, -1, -1):
            j = j0 + q
            # position i reads j <= i, decayed from j to i
            seg = cum[:, j:j + 1] - cumt[j:j + 1, :]
            m = (jnp.exp(jnp.where(tri, seg, -jnp.inf)) * cb).astype(cd)
            yq = jnp.dot(m, xdc, preferred_element_type=f32)
            y = yq if y is None else jnp.where(lane < (q + 1) * P, yq, y)
        # the state the chunk starts from, read by C at every position
        rows = slice(t * W, (t + 1) * W)
        s_in = acc[rows, :]
        y_ref[0, :, lanes] = y + (jax.lax.dot_general(
            Cc, s_in.astype(cd), _NT, preferred_element_type=f32)
            * wide(at, j0))
        # what the chunk adds to the state by its end
        s_c = jax.lax.dot_general(
            (xd * wide(to_end, j0)).astype(cd), Bc, _TN,
            preferred_element_type=f32)
        for q in range(r):
            j, head = j0 + q, slice(t * W + q * P, t * W + (q + 1) * P)
            acc[head, :] = (acc[head, :] * over_ref[head0 + j]
                            + s_c[q * P:(q + 1) * P])

    @pl.when(chunk == nc - 1)
    def _():
        s_ref[0] = acc[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "block"))
def ssm_scan(x, Bm, Cm, dt, A, state, *, chunk: int,
             interpret: Optional[bool] = None, block: Optional[int] = None):
    """:func:`~deepspeed_tpu.models.mixers.ssm._ssm_scan` as one kernel:
    ``x [B,S,H,P]``, ``Bm``/``Cm [B,S,G,N]`` in the compute dtype, ``dt
    [B,S,H]`` float32 and 0 at a masked position, ``A [H]`` float32, ``state
    [B,H,P,N]`` float32 -> ``(y [B,S,H,P] float32, the state after the
    block)``.  A block that is no whole number of chunks is padded with
    masked positions, as there.  Shapes outside :func:`scan_block` raise
    ``NotImplementedError``: the caller keeps :func:`_ssm_scan`.  ``block``
    (``tools/ssm_scan_bench.py``) holds that many heads a grid step in the
    tile plan's place.

    Jitted: a program calls it once a layer, and a traced call a layer is
    the host's time (PERF.md, PR 48)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    Q = chunk
    hb = scan_block(H, G, P, N, Q)
    if hb is not None and block is not None:
        hb = None if (H // G) % block or block * P % LANES else block
    if hb is None or state.dtype != jnp.float32:
        raise NotImplementedError(
            f"ssm_scan has no tile plan for {H} heads of {P} in {G} "
            f"group(s), a {state.dtype} state of {N} columns and chunks of "
            f"{Q}; use the XLA path")
    pad = -S % Q
    if pad:
        x, Bm, Cm, dt = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2)) for a in (x, Bm, Cm, dt))
    Sp, nh, per_group = S + pad, H // hb, H // G
    nc, f32 = Sp // Q, jnp.float32
    # the cumulative sum of dt A inside each chunk (inclusive, <= 0), and
    # with dt a block of heads at a time: [B, S, H] float32, a few MB
    cum = jnp.cumsum((dt * A).reshape(B, nc, Q, H), axis=2).reshape(B, Sp, H)

    def by_block(a):        # [B, S, H] -> [B, H / hb, S, hb]
        return jnp.moveaxis(a.reshape(B, Sp, nh, hb), 2, 1)

    cum_b = by_block(cum)

    def token(b, h, c):
        return (b, c, h)

    def cols(b, h, c):
        return (b, h, c, 0)

    def group(b, h, c):
        return (b, c, h * hb // per_group)

    def carried(b, h, c):
        return (b, h, 0)

    y, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb, P=P),
        grid=(B, nh, nc),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, Q, hb * P), token),
                  pl.BlockSpec((1, 1, Q, hb), cols),
                  pl.BlockSpec((1, 1, Q, hb), cols),
                  pl.BlockSpec((1, 1, hb, Q), lambda b, h, c: (b, h, 0, c)),
                  pl.BlockSpec((1, Q, N), group),
                  pl.BlockSpec((1, Q, N), group),
                  pl.BlockSpec((1, hb * P, N), carried)],
        out_specs=[pl.BlockSpec((1, Q, hb * P), token),
                   pl.BlockSpec((1, hb * P, N), carried)],
        out_shape=[jax.ShapeDtypeStruct((B, Sp, H * P), f32),
                   jax.ShapeDtypeStruct((B, H * P, N), f32)],
        scratch_shapes=[pltpu.VMEM((hb * P, N), f32)],
        compiler_params=parallel_semantics(2, 1),
        interpret=resolve_interpret(interpret), name="ssm_scan",
    )(jnp.exp(cum[:, Q - 1::Q]).reshape(-1), x.reshape(B, Sp, H * P),
      by_block(dt), cum_b, jnp.swapaxes(cum_b, 2, 3),
      Bm.reshape(B, Sp, G * N), Cm.reshape(B, Sp, G * N),
      state.reshape(B, H * P, N))
    return (y.reshape(B, Sp, H, P)[:, :S], state.reshape(B, H, P, N))
