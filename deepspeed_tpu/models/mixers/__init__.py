"""A mixer that keeps a state a slot, said once: each in a module of its
own, and :data:`MIXERS`, the one table the model file
(``models/transformer.py``), the cache's builder and the serving executor
walk where they would branch on the kind.  A fourth kind is a module and a
row here.  The arrows point one way: a mixer's module imports nothing of the
model file and takes a config as it comes; the model file imports this."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from . import conv, delta, ssm
from .common import _slot_rows, _slot_rows_in_place

__all__ = ["MIXERS", "Mixer", "check", "mixers_of", "paged",
           "state_scan_paths", "state_step_paths"]


@dataclasses.dataclass(frozen=True)
class Mixer:
    """What the model file knows of one kind of mixer."""
    # its entry in a ``layer_pattern``, and the config field that turns it on
    kind: str
    field: str
    # what a refusal names it by, and :func:`cache_kind`'s first clause
    words: str
    keeps: str
    # may stand BESIDE attention in one block (Falcon-H1's parallel form);
    # any other is in attention's place wherever it is
    beside: bool
    # the config fields of its convolution's taps and of its scan's chunk
    # (None: a prompt runs no scan)
    taps: str
    chunk: Optional[str]
    # its cache leaves, a row a slot: ``(state, tail)`` or ``(tail,)``
    pool_keys: Tuple[str, ...]
    # ``mesh_info()``'s key for the step a decode tick holds, the rule that
    # names the step, and its passes over a live slot's state a layer
    step_key: str
    step_path: Callable[..., Optional[str]]
    passes: Dict[str, int]
    # refusals(cfg) -> its own ``(on, what)``; param_count(cfg); init(cfg,
    # rng, the model's normal draw) / specs(cfg) -> its leaves of a stack of
    # layers; leaves(cfg, layers, slots, dtype) -> its cache leaves;
    # mixer(cfg, lp, h, seq_mask, kept, [step]) -> (out, kept)
    refusals: Callable
    param_count: Callable
    init: Callable
    specs: Callable
    leaves: Callable
    mixer: Callable
    # a batch's rows of a cache leaf: ``(take, put)``
    rows: Callable = _slot_rows_in_place
    # a state as the leaf keeps it <-> as the recurrence reads it
    pack: Optional[Callable] = None
    unpack: Optional[Callable] = None
    # the step over the state leaf where it lies (``step_path``'s "one_pass")
    one_pass: Optional[Callable] = None
    # a kind whose prompt's scan has a kernel: ``mesh_info()``'s key for the
    # scan a prefill program holds, the rule that names it (scan_path(cfg,
    # tokens, the state leaf's dtype)), and the block's update where the rule
    # says "kernel": the mixer's ``step`` over a state taken from the leaf
    scan_key: Optional[str] = None
    scan_path: Optional[Callable[..., Optional[str]]] = None
    scan: Optional[Callable] = None


_NO_PAGE = (" that no page holds, so a page copied, parked, rescaled or "
            "split by head leaves it behind, and there is nothing to start "
            "a tail from or to go back to")

# Newest first: the order of ``STATE_POOL_KEYS`` (``PAGED_POOL_KEYS``' order
# is the executor's donation index).
MIXERS: Dict[str, Mixer] = {m.kind: m for m in (
    Mixer(kind="conv", field="conv_taps",
          words="conv layers (conv_taps)",
          keeps="gated short-convolution layers (a convolution's tail a "
                "slot): a slot's tail is a row" + _NO_PAGE,
          beside=False, taps="conv_taps", chunk=None,
          pool_keys=("conv_tail",),
          step_key="conv_step", step_path=conv.conv_step_path,
          passes={"plain": 1},
          refusals=conv.refusals, param_count=conv.param_count,
          init=conv.init, specs=conv.specs, leaves=conv.leaves,
          mixer=conv._conv_mixer),
    Mixer(kind="linear", field="linear_heads",
          words="delta layers (linear_heads)",
          keeps="gated-delta-rule layers (a matrix state a head a slot): a "
                "slot's state is one tensor" + _NO_PAGE,
          beside=False, taps="linear_conv", chunk="linear_chunk",
          pool_keys=("delta_state", "delta_conv"),
          step_key="delta_step", step_path=delta.delta_step_path,
          # the plain step: the decay and the update in place, and two
          # reductions that each read the state again
          passes={"one_pass": 1, "plain": 3},
          refusals=delta.refusals, param_count=delta.param_count,
          init=delta.init, specs=delta.specs, leaves=delta.leaves,
          mixer=delta._delta_mixer,
          pack=delta.delta_state_pack, unpack=delta.delta_state_heads,
          one_pass=delta._delta_step_one_pass),
    # beside attention in every layer, or in its place in some
    # (layer_pattern): the leaves differ in depth, the reason does not
    Mixer(kind="ssm", field="ssm_heads",
          words="state-space layers (ssm_heads)",
          keeps="state-space layers (a state a slot): a slot's state is one "
                "tensor" + _NO_PAGE,
          beside=True, taps="ssm_conv", chunk="ssm_chunk",
          pool_keys=("ssm_state", "ssm_conv"),
          step_key="ssm_step", step_path=ssm.ssm_step_path,
          passes={"one_pass": 2, "xla": 3},
          refusals=ssm.refusals, param_count=ssm.param_count,
          init=ssm.init, specs=ssm.specs, leaves=ssm.leaves,
          mixer=ssm._ssm_mixer, rows=_slot_rows,
          one_pass=ssm._ssm_step_one_pass,
          scan_key="ssm_scan", scan_path=ssm.ssm_scan_path,
          scan=ssm._ssm_scan_kernel),
)}


def mixers_of(cfg) -> Tuple[Mixer, ...]:
    """The rows whose field ``cfg`` turns on: a model's config or a group's
    (``layer_groups`` leaves a group its own kind's alone)."""
    return tuple(m for m in MIXERS.values() if getattr(cfg, m.field))


def check(cfg) -> None:
    """What a model with a mixer is built from, and what it leaves out: each
    row's own list, then the one every kind shares, written against the
    row's name.  Takes a model's config or a group's."""
    run = (cfg.layer_pattern or ())[:cfg.num_layers]
    for m in mixers_of(cfg):
        own = m.refusals(cfg)
        if cfg.norm != "rmsnorm" or cfg.activation not in ("swiglu",
                                                           "relu2"):
            raise NotImplementedError(
                f"{m.words} take RMSNorm and a gated MLP (swiglu) or a "
                "squared-ReLU one (relu2)")
        # of layers that are one sublayer the MLP's are a kind too
        other = sorted(set(run) - {m.kind, "full"}
                       - ({"mlp"} if cfg.one_sublayer else set()))
        for on, what in (
                *((True, o.words) for o in mixers_of(cfg) if o is not m),
                *own,
                *((getattr(cfg, flag), flag) for flag in (
                    "parallel_residual", "post_layernorm", "random_ltd")),
                (isinstance(cfg.num_experts, (tuple, list)),
                 "per-layer expert counts (a num_experts tuple)"),
                (bool(cfg.kv_lora_rank), "latent attention"),
                (bool(other), f"{' and '.join(other)} layers in one "
                 "layer_pattern with them"),
                (cfg.attn_bias and (bool(run) or not m.beside),
                 "attn_bias" + (" under a layer_pattern" if m.beside
                                else "")),
                (bool(run) and not (m.kind in run and "full" in run),
                 "a layer_pattern whose layers run are not of both kinds, "
                 f"{m.kind} and full"),
                (cfg.attention_layers is not None, "attention_layers"),
                (cfg.loop_passes > 1, "loop_passes"),
                (cfg.pipeline_stages > 1, "pipeline_stages")):
            if on:
                raise NotImplementedError(f"{m.words} do not take {what}")


def state_step_paths(cfg) -> Dict[str, str]:
    """``{kind: the step a decode tick of cfg holds}`` for the kinds the
    model has (each row's ``step_path``); ``mesh_info()`` reports them."""
    return {m.kind: m.step_path(cfg) for m in mixers_of(cfg)}


def state_scan_paths(cfg, tokens: int = 2) -> Dict[str, Optional[str]]:
    """``{scan_key: the scan a prompt of cfg holds}`` for every row whose
    scan has a kernel (the row's ``scan_path`` over a block of ``tokens``;
    ``None`` for a model without the kind); ``mesh_info()`` reports them."""
    return {m.scan_key: m.scan_path(cfg, tokens)
            for m in MIXERS.values() if m.scan_key}


def paged(m: Mixer, cfg, pools: Dict[str, Any], row0, state_slot, start,
          seq_mask):
    """``_block``'s ``ssm`` for a layer of ``m``'s kind against its cache
    leaves, stacked ``[L * slots, ...]`` with this layer's rows from
    ``row0`` on: the batch's rows are taken (``state_slot`` None: the block
    ``row0 .. row0 + B - 1`` where it lies; else the rows it names), a row
    that starts its sequence begins from zeros, the mixer advances them, and
    they are put back where they were.  What is kept is the leaves.

    The state of a decode tick on a TPU is never taken: where
    ``m.step_path`` says ``"one_pass"`` the step is ``m.one_pass`` over the
    state leaf itself, one read and one write of each row.  A longer block
    whose ``m.scan_path`` says ``"kernel"`` runs its scan as ``m.scan`` over
    the rows taken."""
    B, S = seq_mask.shape
    fresh = (start == 0) & seq_mask.any(axis=1)
    take, put = m.rows(row0, state_slot, B)
    state_key, tail_key = (None, *m.pool_keys)[-2:]    # (tail,): no state
    one_pass = state_key and m.step_path(
        cfg, S, state_slot, pools[state_key].dtype) == "one_pass"
    scan = functools.partial(m.scan, cfg) if m.scan_path and m.scan_path(
        cfg, S, pools[state_key].dtype) == "kernel" else None

    def mixer(lp, h):
        tail = jnp.where(fresh[:, None, None], 0, take(
            pools[tail_key]).reshape(B, getattr(cfg, m.taps) - 1, -1))
        kept = {}
        if state_key is None:   # the tail is all the state there is
            out, tail = m.mixer(cfg, lp, h, seq_mask, tail)
        elif one_pass:
            out, (state, tail) = m.mixer(
                cfg, lp, h, seq_mask, (pools[state_key], tail),
                functools.partial(m.one_pass, row0=row0, fresh=fresh))
            kept[state_key] = state
        else:
            # the recurrence is float32 whatever the leaf is kept in
            state = take(pools[state_key]).astype(jnp.float32)
            if m.unpack is not None:
                state = m.unpack(cfg, state)
            state = jnp.where(fresh[:, None, None, None], 0.0, state)
            out, (state, tail) = m.mixer(cfg, lp, h, seq_mask, (state, tail),
                                         scan)
            if m.pack is not None:
                state = m.pack(cfg, state)
            kept[state_key] = put(pools[state_key], state)
        kept[tail_key] = put(pools[tail_key], tail.reshape(
            B, *pools[tail_key].shape[1:]))
        return out, kept
    return mixer
