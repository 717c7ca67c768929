"""The gated short convolution (LFM2's operator; the "conv" layers of a
``layer_pattern``), in attention's place (``TransformerConfig``'s ``conv_*``
fields say what it is made of).  What the model file knows of it is its row
of :data:`~deepspeed_tpu.models.mixers.MIXERS`."""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import _causal_taps

def _conv_mixer(cfg, lp: Dict[str, Any], h,
                seq_mask=None, tail=None):
    """LFM2's operator on the layer's input ``h [B,S,d]``: ``[B | C | u] =
    h W_in``, ``z = B . u``, the depthwise causal convolution of z behind the
    sequence's ``tail [B, taps - 1, d]`` (None: it starts here, zeros) with
    NO activation, ``(C . conv) W_out``: ``(out [B,S,d], the new tail)``.
    The tail is the last ``taps - 1`` rows of z at REAL positions
    (``seq_mask [B,S]``, real tokens lead; :func:`_causal_taps`), so a
    padded block leaves what the unpadded one does and a row with no real
    token keeps the tail it had.  The projections' outputs, z, the tail and
    the gated sum are of ``h``'s dtype; each product of two of them and the
    three-term sum are taken in float32 and rounded once."""
    B, S, d = h.shape
    f32 = jnp.float32
    if seq_mask is None:
        seq_mask = jnp.ones((B, S), bool)
    if tail is None:
        tail = jnp.zeros((B, cfg.conv_taps - 1, d), h.dtype)
    with jax.named_scope("conv_in"):
        p = h @ lp["conv_in"]
        z = (p[..., :d].astype(f32) * p[..., 2 * d:].astype(f32)
             ).astype(h.dtype)
    with jax.named_scope("conv_taps"):
        c, tail = _causal_taps(lp["conv_w"], lp.get("conv_b"), z, tail,
                               seq_mask.sum(1))
    with jax.named_scope("conv_out"):
        gated = (p[..., d:2 * d].astype(f32) * c).astype(h.dtype)
        out = gated @ lp["conv_out"]
    return out, tail


def conv_step_path(cfg) -> Optional[str]:
    """How a decode tick advances the "conv" layers' tails: ``"plain"`` (the
    three-term sum and the shift, fused by the compiler: a slot's tail is
    two rows, read once and written once), ``None`` for a model with no such
    layer.  The serving executor reports it (``mesh_info()["conv_step"]``)
    beside ``ssm_step`` / ``delta_step``."""
    return "plain" if cfg.conv_taps else None


# -- what its row of the table reads --

def refusals(cfg):
    """A ValueError for taps it cannot be built from, then ``(on, what)``
    for what it refuses that the other mixers do not.  Leading dense layers
    and expert layers it takes: the walk by kind runs each layer its own
    group's MLP."""
    if cfg.conv_taps < 2:
        raise ValueError("conv layers take conv_taps > 1")
    return ((cfg.sandwich_norm or cfg.norm_after,
             "sandwich_norm or norm_after"),)


def param_count(cfg) -> int:
    """In- and out-projection, the taps (and bias)."""
    d = cfg.hidden_size
    return 4 * d * d + (cfg.conv_taps + cfg.conv_bias) * d


def init(cfg, rng, dense) -> Dict[str, Any]:
    """The operator's leaves of ``cfg.num_layers`` layers: the taps
    U(+-1/2), as the other mixers'."""
    L, d = cfg.num_layers, cfg.hidden_size
    down = cfg.initializer_range / math.sqrt(2 * L)
    sk = jax.random.split(jax.random.fold_in(rng, 21), 4)
    layers = dict(
        conv_in=dense(sk[0], (L, d, 3 * d)),
        conv_w=jax.random.uniform(sk[1], (L, cfg.conv_taps, d),
                                  minval=-0.5, maxval=0.5),
        conv_out=dense(sk[2], (L, d, d), down))
    if cfg.conv_bias:
        layers["conv_b"] = dense(sk[3], (L, d))
    return layers


def specs(cfg) -> Dict[str, P]:
    """Whole on every chip, as the other mixers: a slot's tail is one
    row."""
    whole = P(None, None, None)
    layers = dict(conv_in=whole, conv_w=whole, conv_out=whole)
    if cfg.conv_bias:
        layers["conv_b"] = P(None, None)
    return layers


def leaves(cfg, layers: int, slots: int, dtype) -> Dict[str, Any]:
    """The one slot-indexed leaf of ``layers`` conv layers: a slot's ``taps
    - 1`` rows of z side by side in ONE row (2 x 2,048 for LFM2), as
    ``delta_conv`` is kept and for its reason (``mixers/delta.py``)."""
    return {"conv_tail": jnp.zeros(
        (layers, slots, (cfg.conv_taps - 1) * cfg.hidden_size), dtype)}
