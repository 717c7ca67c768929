"""The state-space mixer (Mamba-2; Falcon-H1's beside attention in every
block, Granite 4.0-H's alone in the "ssm" layers of a ``layer_pattern``;
``TransformerConfig``'s ``ssm_*`` fields say what it is made of).  What the
model file knows of it is its row of
:data:`~deepspeed_tpu.models.mixers.MIXERS`."""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import common
from .common import _causal_taps, _scaled

def ssm_widths(cfg) -> Tuple[int, int, int]:
    """``(d_ssm, convolved channels, B's or C's width)`` of the mixer:
    heads x head_dim; x, B and C together; groups x state."""
    d_ssm, gn = cfg.ssm_heads * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state
    return d_ssm, d_ssm + 2 * gn, gn


def ssm_in_width(cfg) -> int:
    """The in-projection's outputs: ``[z | x | B | C | dt]`` (9,248 for
    Falcon-H1-34B)."""
    d_ssm, conv, _ = ssm_widths(cfg)
    return d_ssm + conv + cfg.ssm_heads


def _ssm_project(cfg, lp: Dict[str, Any], h):
    """Post-norm activations ``h [B,S,d]`` through the mixer's
    in-projection, each of its five segments ``[z | x | B | C | dt]`` by its
    own multiplier: ``(z [B,S,d_ssm], xBC [B,S,channels], dt [B,S,H])``,
    ``dt`` in float32 before its bias and softplus."""
    d_ssm, conv, gn = ssm_widths(cfg)
    mup = np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                    (d_ssm, d_ssm, gn, gn, cfg.ssm_heads))
    with jax.named_scope("ssm_in"):
        p = _scaled(h, cfg.ssm_in_multiplier) @ lp["ssm_in"]
        p = p.astype(jnp.float32) * mup
    return (p[..., :d_ssm].astype(h.dtype),
            p[..., d_ssm:d_ssm + conv].astype(h.dtype), p[..., d_ssm + conv:])


def _ssm_scan(cfg, x, Bm, Cm, dt, A, state):
    """The selective state update over a block, in chunks (the SSD form of
    Mamba-2): ``x [B,S,H,P]``, ``Bm``/``Cm [B,S,G,N]``, ``dt [B,S,H]``
    float32 and 0 at a masked position, ``A [H]`` float32 (< 0), ``state
    [B,H,P,N]`` float32 -> ``(y [B,S,H,P] float32, the state after the
    block)`` with

        S_t = exp(dt_t A) S_t-1 + dt_t x_t (x) B_t        y_t = S_t C_t

    Inside a chunk of Q positions the masked product ``(C B^T . decay) (dt
    x)``; between chunks the carried state, decayed over each chunk and read
    by C at every position.  ``dt = 0`` leaves the state as it was and adds
    nothing, so padding behind the real tokens (the bucket's, or up to a
    whole chunk) changes no number.  Decays, cumulative sums and the carried
    state are float32; the four products take the compute dtype's operands
    and accumulate in float32.

    Who runs it is :func:`ssm_scan_path`'s rule: the training forward and
    any plain ``forward`` (it has a derivative, a ``pallas_call`` has none),
    and a serving program off a TPU or at a shape the kernel's tile plan
    refuses; a serving program on a TPU runs :func:`_ssm_scan_kernel`, the
    same arithmetic with a chunk's temporaries held on chip.  It is that
    kernel's yardstick in the tests."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    Hg, Q = H // G, cfg.ssm_chunk
    pad = -S % Q
    if pad:
        x, Bm, Cm, dt = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2)) for a in (x, Bm, Cm, dt))
    nc, f32, cd = (S + pad) // Q, jnp.float32, x.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)
    with jax.named_scope("ssm_scan"):
        a = (dt * A).reshape(B, nc, Q, G, Hg)
        cum = jnp.cumsum(a, axis=2)                     # inclusive, <= 0
        xd = (x.astype(f32) * dt[..., None]).reshape(B, nc, Q, G, Hg, P)
        Bc, Cc = Bm.reshape(B, nc, Q, G, N), Cm.reshape(B, nc, Q, G, N)
        # within a chunk: position i reads j <= i, decayed from j to i
        seg = cum[:, :, :, None] - cum[:, :, None, :]   # [B,nc,i,j,G,Hg]
        tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
        m = (jnp.exp(jnp.where(tri, seg, -jnp.inf))
             * jnp.moveaxis(mm("bcign,bcjgn->bcgij", Cc, Bc), 2, 4)[..., None])
        y = mm("bcijgk,bcjgkp->bcigkp", m.astype(cd), xd.astype(cd))
        # what each chunk adds to the state by its end, and the state each
        # chunk starts from
        to_end = jnp.exp(cum[:, :, -1:] - cum)
        s_c = mm("bcjgkp,bcjgn->bcgkpn", (xd * to_end[..., None]).astype(cd),
                 Bc)
        over = jnp.exp(cum[:, :, -1])                   # [B,nc,G,Hg]

        def chunk(s, sc_over):
            sc, t = sc_over
            return s * t[..., None, None] + sc, s

        state, s_in = jax.lax.scan(
            chunk, state.reshape(B, G, Hg, P, N),
            (jnp.moveaxis(s_c, 1, 0), jnp.moveaxis(over, 1, 0)))
        y = y + (mm("bcign,bcgkpn->bcigkp", Cc,
                    jnp.moveaxis(s_in, 0, 1).astype(cd))
                 * jnp.exp(cum)[..., None])
    return (y.reshape(B, S + pad, H, P)[:, :S], state.reshape(B, H, P, N))


def ssm_scan_path(cfg, tokens: int, dtype=jnp.float32) -> Optional[str]:
    """Which scan a paged program of ``tokens`` a row holds for ``cfg``'s
    state-space layers: ``"kernel"`` (``ops/pallas/ssm_scan.py``: a chunk's
    decays, its four products and the carried state in on-chip memory, ``x``
    read and ``y`` written once where the mixer holds them) for a block of
    more than one token over a float32 state, on a TPU, at a shape the
    kernel's tile plan takes; ``"xla"`` (:func:`_ssm_scan`) for any other
    block; ``None`` for one token a row (:func:`ssm_step_path`) and a model
    with no such layer.  Read at trace time from what the code can observe;
    the serving executor reports it (``mesh_info()["ssm_scan"]``, the
    ``ssm_scan`` attr of a ``serve.prefill`` span).  The training forward
    and ``forward`` never ask: they keep :func:`_ssm_scan`."""
    from ...ops.pallas.ssm_scan import scan_block

    if not cfg.ssm_heads or tokens <= 1:
        return None
    if (dtype == jnp.float32 and common._pallas_interpret() is not None
            and scan_block(cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim,
                           cfg.ssm_state, cfg.ssm_chunk) is not None):
        return "kernel"
    return "xla"


def _ssm_scan_kernel(cfg, x, Bm, Cm, dt, A, state):
    """:func:`_ssm_scan` as the one kernel of ``ops/pallas/ssm_scan.py``,
    the same formula term for term; the ``step`` of a block where
    :func:`ssm_scan_path` says ``"kernel"``."""
    from ...ops.pallas.ssm_scan import ssm_scan

    with jax.named_scope("ssm_scan"):
        return ssm_scan(x, Bm, Cm, dt, A, state, chunk=cfg.ssm_chunk,
                        interpret=common._pallas_interpret())


def _ssm_step(cfg, x, Bm, Cm, dt, A, state):
    """:func:`_ssm_scan` for one token a row: the recurrence itself, every
    number float32.  The sum over the state's columns is written out (a
    product, then a reduction) so that no matrix unit rounds the state to
    read it.  A masked row (``dt = 0``) keeps its state.

    Who runs it is :func:`ssm_step_path`'s rule; the compiler makes an
    in-place update of it and a reduction that reads the state again, three
    passes.  It is the yardstick of :func:`_ssm_step_one_pass` in the tests."""
    B, _, H, P = x.shape
    G, N = Bm.shape[2:]
    Hg, f32 = H // G, jnp.float32
    with jax.named_scope("ssm_step"):
        dt1 = dt[:, 0].reshape(B, G, Hg)
        xd = x[:, 0].astype(f32).reshape(B, G, Hg, P) * dt1[..., None]
        s = (state.reshape(B, G, Hg, P, N)
             * jnp.exp(dt1 * A.reshape(G, Hg))[..., None, None]
             + xd[..., None] * Bm[:, 0].astype(f32)[:, :, None, None, :])
        y = (s * Cm[:, 0].astype(f32)[:, :, None, None, :]).sum(-1)
    return y.reshape(B, 1, H, P), s.reshape(B, H, P, N)


def ssm_step_path(cfg, tokens: int = 1,
                  state_slot=None, dtype=jnp.float32) -> Optional[str]:
    """Which step a paged program of ``tokens`` a row holds for ``cfg``'s
    state-space layers: ``"one_pass"`` (``ops/pallas/ssm_step.py``: the pool
    leaf updated in place and ``y`` read from the block in on-chip memory,
    one read and one write of a slot's state) for one token a row over
    contiguous slot rows (``state_slot`` None: a decode tick) of a float32
    leaf, on a TPU, at a shape the kernel's tile plan takes; ``"xla"``
    (:func:`_ssm_step`, three passes) for any other single token; ``None``
    for a longer block (:func:`_ssm_scan`) and a model with no such layer.
    Read at trace time from what the code can observe; the serving executor
    reports it (``mesh_info()["ssm_step"]``)."""
    from ...ops.pallas.ssm_step import head_block

    if not cfg.ssm_heads or tokens != 1:
        return None
    if (state_slot is None and dtype == jnp.float32
            and common._pallas_interpret() is not None
            and head_block(cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_head_dim,
                           cfg.ssm_state) is not None):
        return "one_pass"
    return "xla"


def _ssm_step_one_pass(x, Bm, Cm, dt, A, leaf, row0, fresh):
    """:func:`_ssm_step` for the rows ``row0 .. row0 + B - 1`` of the stacked
    cache leaf ``leaf [L * slots, H, P, N]`` where they lie: ``(y
    [B,1,H,P] float32, the leaf)``, the same formula for the state and for
    ``y`` term for term, a ``fresh [B]`` row from zeros."""
    from ...ops.pallas.ssm_step import ssm_step

    with jax.named_scope("ssm_step"):
        dt1 = dt[:, 0]
        leaf, y = ssm_step(
            leaf, row0, fresh, jnp.exp(dt1 * A),
            x[:, 0].astype(jnp.float32) * dt1[..., None],
            Bm[:, 0].astype(jnp.float32), Cm[:, 0].astype(jnp.float32),
            interpret=common._pallas_interpret())
    return y[:, None], leaf


def _ssm_gate_norm(cfg, lp: Dict[str, Any], y, z):
    """The mixer's output gated by ``silu(z)`` and THEN RMS-normed within
    each of the ``ssm_groups`` groups of channels (``mamba_rms_norm``,
    ``mamba_norm_before_gate`` false), in float32."""
    B, S, d_ssm = y.shape
    with jax.named_scope("ssm_gate_norm"):
        g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
             ).reshape(B, S, cfg.ssm_groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + cfg.norm_eps)
        return (g.reshape(B, S, d_ssm)
                * lp["ssm_norm_scale"].astype(jnp.float32)).astype(cfg.dtype)


def _ssm_start(cfg, rows: int, dtype):
    """``(state, tail)`` of ``rows`` sequences that start here: zeros."""
    return (jnp.zeros((rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                      jnp.float32),
            jnp.zeros((rows, cfg.ssm_conv - 1, ssm_widths(cfg)[1]), dtype))


# Positions of one prompt the mixer takes at a time: the in-projection's
# output is 16,768 wide and :func:`_ssm_scan` keeps ``[chunk, chunk, heads]``
# float32 a chunk (the decays between every two positions), together 3 GB
# over a 16,384-token block of 128 heads in chunks of 256 and 0.4 GB over
# 2,048 of them.  A serving program on a TPU runs the scan as
# ``ops/pallas/ssm_scan.py`` (:func:`ssm_scan_path`), which keeps the decays
# on chip: a piece is then the in-projection's alone, a kernel call a piece
# with the state carried between them.  The training forward, ``forward``
# and the tests run :func:`_ssm_scan`
SSM_BLOCK_TOKENS = 2048


def _ssm_mixer(cfg, lp: Dict[str, Any], h, seq_mask=None,
               kept=None, step=None):
    """:func:`_ssm_mixer_block` over a block of any length: one longer than
    ``SSM_BLOCK_TOKENS`` (in whole pieces of that many) runs as one scan on
    the device over the pieces, the state and the convolution's tail carried
    from piece to piece as they are from call to call, so that the
    temporaries are a piece's and not the prompt's.  The same numbers either
    way: real tokens lead the block, so they lead every piece.  ``step``
    (a block's: :func:`_ssm_scan_kernel`) is every piece's."""
    B, S, _ = h.shape
    n = SSM_BLOCK_TOKENS
    if S <= n or S % n:
        return _ssm_mixer_block(cfg, lp, h, seq_mask, kept, step)
    if seq_mask is None:
        seq_mask = jnp.ones((B, S), bool)
    if kept is None:
        kept = _ssm_start(cfg, B, h.dtype)

    def pieces(a):      # [B, S, ...] -> [S / n, B, n, ...]
        return jnp.moveaxis(a.reshape(B, S // n, n, *a.shape[2:]), 1, 0)

    def piece(kept, xs):
        out, kept = _ssm_mixer_block(cfg, lp, xs[0], xs[1], kept, step)
        return kept, out

    kept, out = jax.lax.scan(piece, kept, (pieces(h), pieces(seq_mask)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, -1), kept


def _ssm_mixer_block(cfg, lp: Dict[str, Any], h,
                     seq_mask=None, kept=None, step=None):
    """The Mamba-2 mixer of a block on its post-norm input ``h [B,S,d]``:
    in-projection, convolution, selective state update (one token a row:
    :func:`_ssm_step`, a longer block: :func:`_ssm_scan`), the skip ``D x``,
    gated norm, out-projection.  ``kept = (state [B,H,P,N] float32, tail
    [B,K-1,C])`` is what the rows' sequences hold so far (``None``: they
    start here); ``seq_mask [B,S]`` its real tokens, which lead the block.
    Returns ``(out [B,S,d], (state, tail) after the block's real tokens)``.
    ``step(x, Bm, Cm, dt, A, state) -> (y, state)`` stands in for the state
    update where the caller holds the state in another form (a decode
    tick's pool leaf: :func:`~deepspeed_tpu.models.mixers.paged`; ``state``
    is then whatever it takes and returns) or runs it another way (a
    prompt's block in a serving program: :func:`_ssm_scan_kernel`)."""
    B, S, _ = h.shape
    H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    d_ssm, conv, gn = ssm_widths(cfg)
    if seq_mask is None:
        seq_mask = jnp.ones((B, S), bool)
    state, tail = kept if kept is not None else _ssm_start(cfg, B, h.dtype)
    z, xbc, dt = _ssm_project(cfg, lp, h)
    # the new tail is gathered from the last K - 1 REAL positions: a padded
    # prompt leaves what the unpadded one does
    n_real = seq_mask.sum(1)
    with jax.named_scope("ssm_conv"):
        y, tail = _causal_taps(lp["ssm_conv_w"], lp["ssm_conv_b"], xbc, tail,
                               n_real)
        xbc = jax.nn.silu(y).astype(xbc.dtype)
    x = xbc[..., :d_ssm].reshape(B, S, H, P)
    Bm = xbc[..., d_ssm:d_ssm + gn].reshape(B, S, G, N)
    Cm = xbc[..., d_ssm + gn:].reshape(B, S, G, N)
    dt = jnp.where(seq_mask[..., None], jax.nn.softplus(
        dt + lp["ssm_dt_bias"].astype(jnp.float32)), 0.0)
    A = -jnp.exp(lp["ssm_A_log"].astype(jnp.float32))
    y, state = (step or functools.partial(
        _ssm_step if S == 1 else _ssm_scan, cfg))(x, Bm, Cm, dt, A, state)
    D = lp["ssm_D"].astype(jnp.float32)
    if step is not None and S > 1:
        # a block's kernel reads x and writes y as ``[B,S,d_ssm]``; the skip
        # stays in that order (a float32 ``[.., H, 64]`` is laid out
        # position-minor on the TPU: a copy of y and of x in, one out)
        y = y.reshape(B, S, d_ssm) + jnp.repeat(D, P) * xbc[
            ..., :d_ssm].astype(jnp.float32)
    else:
        y = (y + D[:, None] * x.astype(jnp.float32)).reshape(B, S, d_ssm)
    with jax.named_scope("ssm_out"):
        out = _ssm_gate_norm(cfg, lp, y, z) @ lp["ssm_out"]
    return out, (state, tail)


# -- what its row of the table reads --

def refusals(cfg):
    """A ValueError for widths it cannot be built from, then ``(on, what)``
    for what it refuses that the other mixers do not."""
    if not (cfg.ssm_head_dim and cfg.ssm_state and cfg.ssm_conv > 1
            and cfg.ssm_heads % cfg.ssm_groups == 0
            and len(cfg.ssm_multipliers) == 5
            and len(cfg.mlp_multipliers) == 2):
        raise ValueError(
            "state-space layers (ssm_heads) take ssm_head_dim, ssm_state, "
            "ssm_conv > 1, heads in whole groups, five ssm_multipliers and "
            "two mlp_multipliers")
    return ((cfg.dense_layers > 0, "leading dense layers (dense_layers)"),
            (cfg.sandwich_norm or cfg.norm_after,
             "sandwich_norm or norm_after"))


def param_count(cfg) -> int:
    """In- and out-projection, the convolution with its bias, A, D, dt's
    bias, the gated norm."""
    d = cfg.hidden_size
    ds, conv = ssm_widths(cfg)[:2]
    return (d * ssm_in_width(cfg) + ds * d + conv * (cfg.ssm_conv + 1)
            + 3 * cfg.ssm_heads + ds)


def init(cfg, rng, dense) -> Dict[str, Any]:
    """The mixer's leaves of ``cfg.num_layers`` layers.  What a normal draw
    would make meaningless gets Mamba-2's own initial ranges: A = -U(1, 16)
    as its log, dt's bias the inverse softplus of a log-uniform step in
    [1e-3, 1e-1], D = 1, the taps U(+-1/2) (1 / sqrt(taps) at 4)."""
    L, d = cfg.num_layers, cfg.hidden_size
    down = cfg.initializer_range / math.sqrt(2 * L)
    H, K = cfg.ssm_heads, cfg.ssm_conv
    d_ssm, conv, _ = ssm_widths(cfg)
    sk = jax.random.split(jax.random.fold_in(rng, 19), 6)
    step = jnp.exp(jax.random.uniform(
        sk[3], (L, H), minval=math.log(1e-3), maxval=math.log(1e-1)))
    return dict(
        ssm_in=dense(sk[0], (L, d, ssm_in_width(cfg))),
        ssm_conv_w=jax.random.uniform(sk[1], (L, K, conv), minval=-0.5,
                                      maxval=0.5),
        ssm_conv_b=dense(sk[2], (L, conv)),
        ssm_dt_bias=step + jnp.log(-jnp.expm1(-step)),
        ssm_A_log=jnp.log(jax.random.uniform(sk[4], (L, H), minval=1.0,
                                             maxval=16.0)),
        ssm_D=jnp.ones((L, H)),
        ssm_norm_scale=jnp.ones((L, d_ssm)),
        ssm_out=dense(sk[5], (L, d_ssm, d), down))


def specs(cfg) -> Dict[str, P]:
    """The mixer whole on every chip: its heads share B and C by group and
    a slot's state is one tensor (sharding them is ROADMAP R5's)."""
    whole, rep = P(None, None, None), P(None, None)
    return dict(ssm_in=whole, ssm_out=whole, ssm_conv_w=whole, ssm_conv_b=rep,
                ssm_dt_bias=rep, ssm_A_log=rep, ssm_D=rep, ssm_norm_scale=rep)


def leaves(cfg, layers: int, slots: int, dtype) -> Dict[str, Any]:
    """The two slot-indexed leaves of ``layers`` layers with the mixer: the
    float32 state and the convolution's tail, a row a slot."""
    return {"ssm_state": jnp.zeros(
                (layers, slots, cfg.ssm_heads, cfg.ssm_head_dim,
                 cfg.ssm_state), jnp.float32),
            "ssm_conv": jnp.zeros(
                (layers, slots, cfg.ssm_conv - 1, ssm_widths(cfg)[1]), dtype)}
