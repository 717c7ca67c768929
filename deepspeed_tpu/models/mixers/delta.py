"""The gated-delta-rule mixer (Gated DeltaNet, arXiv:2412.06464; Olmo-Hybrid's
"linear" layers of a ``layer_pattern``), in attention's place
(``TransformerConfig``'s ``linear_*`` fields say what it is made of).  What
the model file knows of it is its row of
:data:`~deepspeed_tpu.models.mixers.MIXERS`."""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import common
from .common import _causal_taps

def delta_widths(cfg) -> Tuple[int, int, int]:
    """``(keys' width, values' width, convolved channels)`` of the delta
    mixer: heads x key dim (q's and k's each), heads x value dim, q, k and
    v together (2,880, 5,760 and 11,520 for Olmo-Hybrid-7B)."""
    dk = cfg.linear_heads * cfg.linear_key_dim
    dv = cfg.linear_heads * cfg.linear_value_dim
    return dk, dv, 2 * dk + dv


def delta_in_width(cfg) -> int:
    """The in-projection's outputs: ``[q | k | v | gate]`` (17,280)."""
    return delta_widths(cfg)[2] + delta_widths(cfg)[1]


def delta_pack(cfg) -> int:
    """Heads of a delta layer whose value columns share one row of the
    ``delta_state`` leaf, ``[.., heads / pack, key dim, pack x value dim]``:
    the fewest that make the row whole 128-lane tiles (2 at 192 columns: 384
    lanes and nothing padded, where ``[.., 96, 192]`` pads each row to 256),
    1 where no count of heads does."""
    for p in range(1, 5):
        if (p * cfg.linear_value_dim) % 128 == 0 and cfg.linear_heads % p == 0:
            return p
    return 1


def _delta_project(cfg, lp: Dict[str, Any], h):
    """The layer's input ``h [B,S,d]`` through the delta mixer's projections:
    ``(qkv [B,S,channels] before the convolution, the output gate's
    pre-activation [B,S,H*dv], b [B,S,H], a [B,S,H])``, ``b`` and ``a`` in
    float32 (the write strength's and the decay's pre-activations)."""
    conv = delta_widths(cfg)[2]
    with jax.named_scope("delta_in"):
        p = h @ lp["delta_in"]
        ba = (h @ lp["delta_ba"]).astype(jnp.float32)
    H = cfg.linear_heads
    return p[..., :conv], p[..., conv:], ba[..., :H], ba[..., H:]


def _unit_lower_inverse(A):
    """``(I + A)^-1 - I`` for strictly lower-triangular ``A [.., C, C]``
    float32, by forward substitution a row at a time on the vector unit
    (row ``i`` is ``-A_i - sum_j<i A_ij row_j``): backward stable whatever
    the keys are, where a product of powers of ``A`` is not."""
    C = A.shape[-1]

    def row(i, T):
        r = jax.lax.dynamic_index_in_dim(T, i, axis=-2, keepdims=False)
        new = r + (r[..., :, None] * T).sum(-2)
        return jax.lax.dynamic_update_index_in_dim(T, new, i, axis=-2)

    return jax.lax.fori_loop(1, C, row, -A)


def _delta_scan(cfg, q, k, v, g, beta, state):
    """The gated delta rule over a block, in chunks (the WY / UT form of
    Gated DeltaNet): ``q``/``k [B,S,H,dk]`` (L2-normed, q scaled), ``v
    [B,S,H,dv]``, ``g [B,S,H]`` float32 (the log decay, <= 0) and ``beta
    [B,S,H]`` float32, both 0 at a masked position, ``state [B,H,dk,dv]``
    float32 -> ``(o [B,S,H,dv] float32, the state after the block)`` with

        S_t = a_t S_t-1 + b_t k_t (v_t - (a_t S_t-1)^T k_t)^T     o_t = S_t^T q_t

    Inside a chunk of C positions, with ``c`` the running sum of ``g``: ``A
    = strict-lower(diag(b) (K K^T . e^(c_i - c_j)))``, ``T = (I + A)^-1
    diag(b)``, ``W = T (K . e^c)``, ``U = T V``; between chunks the carried
    state: ``V' = U - W S``, ``O = (Q . e^c) S + (Q K^T . e^(c_i - c_j) .
    lower) V'``, ``S <- e^(c_C) S + (K . e^(c_C - c))^T V'``.  ``g = beta =
    0`` leaves the state as it was and adds nothing, so padding behind the
    real tokens changes no number.  Decays, sums, the substitution and the
    carried state are float32; the products take the compute dtype's
    operands and accumulate in float32."""
    B, S, H, dk = q.shape
    C = cfg.linear_chunk
    pad = -S % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (a.ndim - 2)) for a in (q, k, v, g, beta))
    nc, f32, cd = (S + pad) // C, jnp.float32, v.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)

    def chunks(a):      # [B, S, H, ...] -> [nc, B, H, C, ...]
        a = a.reshape(B, nc, C, H, *a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    with jax.named_scope("delta_scan"):
        qc, kc, vc = chunks(q), chunks(k), chunks(v)
        bc = chunks(beta)
        cum = jnp.cumsum(chunks(g), axis=-1)            # inclusive, <= 0
        seg = cum[..., :, None] - cum[..., None, :]     # [nc,B,H,i,j]
        incl = jnp.tril(jnp.ones((C, C), bool))
        decay = jnp.exp(jnp.where(incl, seg, -jnp.inf))
        kk = mm("nbhik,nbhjk->nbhij", kc, kc)
        A = jnp.where(jnp.tril(incl, -1), bc[..., None] * kk * decay, 0.0)
        T = _unit_lower_inverse(A) + jnp.eye(C, dtype=f32)
        e = jnp.exp(cum)[..., None]
        rhs = bc[..., None] * jnp.concatenate(
            [kc.astype(f32) * e, vc.astype(f32)], axis=-1)
        WU = mm("nbhij,nbhjx->nbhix", T.astype(cd), rhs.astype(cd))
        qk = (mm("nbhik,nbhjk->nbhij", qc, kc) * decay).astype(cd)
        qe = (qc.astype(f32) * e).astype(cd)
        to_end = jnp.exp(cum[..., -1:] - cum)[..., None]
        ke = (kc.astype(f32) * to_end).astype(cd)
        over = jnp.exp(cum[..., -1])                    # [nc,B,H]

        def chunk(s, xs):
            wu, qk, qe, ke, over = xs
            sc = s.astype(cd)
            vp = (wu[..., dk:] - mm("bhck,bhkv->bhcv", wu[..., :dk].astype(cd),
                                    sc)).astype(cd)
            o = mm("bhck,bhkv->bhcv", qe, sc) + mm("bhij,bhjv->bhiv", qk, vp)
            return (s * over[..., None, None]
                    + mm("bhck,bhcv->bhkv", ke, vp)), o

        state, o = jax.lax.scan(chunk, state, (WU, qk, qe, ke, over))
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)   # [B,nc,C,H,dv]
    return o.reshape(B, S + pad, H, -1)[:, :S], state


def _delta_step(cfg, q, k, v, g, beta, state):
    """:func:`_delta_scan` for one token a row: the recurrence itself, every
    number float32, the two reads of the state (``S^T k`` of the decayed
    state, ``S^T q`` of the new one) products and reductions on the vector
    unit.  A masked row (``g = beta = 0``) keeps its state.

    Who runs it is :func:`delta_step_path`'s rule; it is the yardstick of
    :func:`_delta_step_one_pass` in the tests."""
    f32 = jnp.float32
    with jax.named_scope("delta_step"):
        q1, k1, v1 = (a[:, 0].astype(f32) for a in (q, k, v))
        s = state * jnp.exp(g[:, 0])[..., None, None]
        u = (s * k1[..., None]).sum(-2)                 # [B,H,dv]
        s = s + k1[..., None] * (beta[:, 0][..., None] * (v1 - u))[..., None, :]
        o = (s * q1[..., None]).sum(-2)
    return o[:, None], s


def delta_state_pack(cfg, state):
    """``[.., H, dk, dv]`` -> the ``delta_state`` leaf's row ``[.., H / p,
    dk, p x dv]`` (:func:`delta_pack`): head ``h``'s columns at ``(h % p) x
    dv`` of row block ``h // p``."""
    p = delta_pack(cfg)
    if p == 1:
        return state
    *lead, H, dk, dv = state.shape
    s = state.reshape(*lead, H // p, p, dk, dv)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, H // p, dk, p * dv)


def delta_state_heads(cfg, leaf):
    """:func:`delta_state_pack`'s inverse: a leaf's rows as ``[.., H, dk,
    dv]``."""
    p = delta_pack(cfg)
    if p == 1:
        return leaf
    *lead, Hp, dk, pdv = leaf.shape
    s = leaf.reshape(*lead, Hp, dk, p, pdv // p)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, Hp * p, dk, pdv // p)


def delta_step_path(cfg, tokens: int = 1,
                    state_slot=None, dtype=jnp.float32) -> Optional[str]:
    """``ssm_step_path`` (``mixers/ssm.py``) for the delta layers: ``"one_pass"``
    (``ops/pallas/delta_step.py``: the pool leaf updated in place, both
    reads of a slot's state from the block in on-chip memory) for a decode
    tick over a float32 leaf on a TPU at a shape the kernel's tile plan
    takes; ``"plain"`` (:func:`_delta_step`) for any other single token;
    ``None`` for a longer block and a model with no such layer."""
    from ...ops.pallas.delta_step import head_block

    if not cfg.linear_heads or tokens != 1:
        return None
    p = delta_pack(cfg)
    if (state_slot is None and dtype == jnp.float32
            and common._pallas_interpret() is not None
            and head_block(cfg.linear_heads // p, cfg.linear_key_dim,
                           p * cfg.linear_value_dim) is not None):
        return "one_pass"
    return "plain"


def _delta_step_one_pass(q, k, v, g, beta, leaf, row0, fresh):
    """:func:`_delta_step` for the rows ``row0 .. row0 + B - 1`` of the
    stacked cache leaf ``leaf [L * slots, H / p, dk, p * dv]`` where they
    lie: ``(o [B,1,H,dv] float32, the leaf)``, a ``fresh [B]`` row from
    zeros."""
    from ...ops.pallas.delta_step import delta_step

    f32 = jnp.float32
    with jax.named_scope("delta_step"):
        leaf, o = delta_step(
            leaf, row0, fresh, jnp.exp(g[:, 0]), beta[:, 0],
            q[:, 0].astype(f32), k[:, 0].astype(f32), v[:, 0].astype(f32),
            interpret=common._pallas_interpret())
    return o[:, None], leaf


def _delta_gate_norm(cfg, lp: Dict[str, Any], o, gate):
    """The mixer's output RMS-normed WITHIN each head (one learned scale of
    ``linear_value_dim`` for all heads) and then gated by ``silu(gate)``,
    in float32: ``o [B,S,H,dv]``, ``gate [B,S,H*dv]`` -> ``[B,S,H*dv]``."""
    B, S, H, dv = o.shape
    with jax.named_scope("delta_gate_norm"):
        o = o.astype(jnp.float32)
        o = (o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                               + cfg.norm_eps)
             * lp["delta_norm_scale"].astype(jnp.float32))
        return (o.reshape(B, S, H * dv)
                * jax.nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype)


def _delta_start(cfg, rows: int, dtype):
    """``(state, tail)`` of ``rows`` sequences that start here: zeros."""
    return (jnp.zeros((rows, cfg.linear_heads, cfg.linear_key_dim,
                       cfg.linear_value_dim), jnp.float32),
            jnp.zeros((rows, cfg.linear_conv - 1, delta_widths(cfg)[2]),
                      dtype))


def _delta_mixer(cfg, lp: Dict[str, Any], h,
                 seq_mask=None, kept=None, step=None):
    """The gated-delta-rule mixer of a block on the layer's input ``h
    [B,S,d]``: projections, convolution, L2 norm of q and k by head, decay
    and write strength, the delta rule (one token a row: :func:`_delta_step`,
    a longer block: :func:`_delta_scan`), norm by head, gate,
    out-projection.  ``kept``, ``seq_mask``, ``step`` and the result as the
    state-space mixer's (``mixers/ssm.py``), the state ``[B,H,dk,dv]``
    float32."""
    B, S, _ = h.shape
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    wk = delta_widths(cfg)[0]
    f32 = jnp.float32
    if seq_mask is None:
        seq_mask = jnp.ones((B, S), bool)
    state, tail = kept if kept is not None else _delta_start(cfg, B, h.dtype)
    qkv, gate, b, a = _delta_project(cfg, lp, h)
    # no bias, and float32 out (the L2 norms read it so)
    n_real = seq_mask.sum(1)
    with jax.named_scope("delta_conv"):
        qkv, tail = _causal_taps(lp["delta_conv_w"], None, qkv, tail, n_real)
        qkv = jax.nn.silu(qkv)

    def unit(x):        # float32 [B,S,H,dk], L2-normed by head
        x = x.reshape(B, S, H, dk)
        return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)

    q = (unit(qkv[..., :wk]) * dk ** -0.5).astype(h.dtype)
    k = unit(qkv[..., wk:2 * wk]).astype(h.dtype)
    v = qkv[..., 2 * wk:].reshape(B, S, H, dv).astype(h.dtype)
    live = seq_mask[..., None]
    beta = jnp.where(live, jax.nn.sigmoid(b) * (
        2.0 if cfg.linear_neg_eigval else 1.0), 0.0)
    g = jnp.where(live, -jnp.exp(lp["delta_A_log"].astype(f32))
                  * jax.nn.softplus(a + lp["delta_dt_bias"].astype(f32)), 0.0)
    o, state = (step or functools.partial(
        _delta_step if S == 1 else _delta_scan, cfg))(q, k, v, g, beta, state)
    with jax.named_scope("delta_out"):
        out = _delta_gate_norm(cfg, lp, o, gate) @ lp["delta_out"]
    return out, (state, tail)


# -- what its row of the table reads --

def refusals(cfg):
    """A ValueError for widths it cannot be built from, then ``(on, what)``
    for what it refuses that the other mixers do not."""
    if not (cfg.linear_key_dim and cfg.linear_value_dim
            and cfg.linear_conv > 1 and cfg.linear_chunk > 0):
        raise ValueError(
            "delta layers (linear_heads) take linear_key_dim, "
            "linear_value_dim, linear_conv > 1 and linear_chunk > 0")
    return ((cfg.num_experts != 1, "expert layers"),
            (cfg.dense_layers > 0, "leading dense layers (dense_layers)"))


def param_count(cfg) -> int:
    """q, k, v and the gate, b and a, the output projection, the taps, A,
    dt's bias, the norm by head."""
    d = cfg.hidden_size
    _, dv, qkv = delta_widths(cfg)
    return (d * (delta_in_width(cfg) + 2 * cfg.linear_heads) + dv * d
            + cfg.linear_conv * qkv + 2 * cfg.linear_heads
            + cfg.linear_value_dim)


def init(cfg, rng, dense) -> Dict[str, Any]:
    """The mixer's leaves of ``cfg.num_layers`` layers.  ``A_log`` and dt's
    bias are Mamba-2's draws (the decay ``exp(-A softplus(a + dt_bias))``
    then lies in (0.2, 1) where a normal draw would leave every head at one
    rate), the taps U(+-1/2), the norm by head 1."""
    L, d = cfg.num_layers, cfg.hidden_size
    down = cfg.initializer_range / math.sqrt(2 * L)
    H, K = cfg.linear_heads, cfg.linear_conv
    _, dv, conv = delta_widths(cfg)
    sk = jax.random.split(jax.random.fold_in(rng, 20), 6)
    step = jnp.exp(jax.random.uniform(
        sk[3], (L, H), minval=math.log(1e-3), maxval=math.log(1e-1)))
    return dict(
        delta_in=dense(sk[0], (L, d, delta_in_width(cfg))),
        delta_ba=dense(sk[1], (L, d, 2 * H)),
        delta_conv_w=jax.random.uniform(sk[2], (L, K, conv), minval=-0.5,
                                        maxval=0.5),
        delta_dt_bias=step + jnp.log(-jnp.expm1(-step)),
        delta_A_log=jnp.log(jax.random.uniform(sk[4], (L, H), minval=1.0,
                                               maxval=16.0)),
        delta_norm_scale=jnp.ones((L, cfg.linear_value_dim)),
        delta_out=dense(sk[5], (L, dv, d), down))


def specs(cfg) -> Dict[str, P]:
    """Whole on every chip, as the state-space mixer: a slot's state is one
    tensor (heads over chips: ROADMAP R5)."""
    whole, rep = P(None, None, None), P(None, None)
    return dict(delta_in=whole, delta_ba=whole, delta_conv_w=whole,
                delta_dt_bias=rep, delta_A_log=rep, delta_norm_scale=rep,
                delta_out=whole)


def leaves(cfg, layers: int, slots: int, dtype) -> Dict[str, Any]:
    """The two slot-indexed leaves of ``layers`` delta layers: the float32
    matrix states, ``pack`` heads' value columns a row
    (:func:`delta_state_pack`), and the three convolutions' tail, a slot's
    ``taps - 1`` inputs side by side in ONE row (kept ``[.., 3, 11520]`` the
    3 pads to a tile of 16 sublanes, 5.3 x the bytes, and every layer of a
    prompt re-lays the leaf out around its update: 39 ms a prompt on the
    v5e, PERF.md PR 51)."""
    p = delta_pack(cfg)
    return {"delta_state": jnp.zeros(
                (layers, slots, cfg.linear_heads // p, cfg.linear_key_dim,
                 p * cfg.linear_value_dim), jnp.float32),
            "delta_conv": jnp.zeros(
                (layers, slots,
                 (cfg.linear_conv - 1) * delta_widths(cfg)[2]), dtype)}
