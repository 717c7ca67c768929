"""Bytes and operations of a decode tick and of a prompt's prefill of a
model whose layers are ONE sublayer each (a state-space mixer, attention, or
an expert layer alone: ``one_sublayer``) and whose routed experts work in a
latent narrower than the model (``moe_latent_size``), from shapes and the
program's counters (``state_slots``, ``state_layers``, ``kv_live_rows``,
``experts_touched_held`` of the ``serve.decode`` spans; ``tokens``,
``pairs_held`` of the ``serve.prefill`` spans).  The yardstick, like
``flops.py``, ``moe_work.py`` and ``ssm_moe_work.py``: what the algorithm
needs, never what the compiled program executes.

By the letters of Nemotron-3-Super's pattern, at its widths: an M layer is
the mixer and its one norm, 109,640,064 parameters; a * layer q, k, v, o and
its norm, 35,655,680; an E layer outside its routed experts the router at
its full width with its bias, the two latent projections, the shared expert
and its norm, 54,530,560, and one routed expert 2 x 1,024 x 2,688 =
5,505,024.  Five M, one * and five E of 128 held experts with the embedding's
and the head's slices (268,435,456) and the final norm are 4,648,163,712 =
9.30 GB in bfloat16; the model whole (88 layers, 512 experts, 131,072 ids)
120,668,707,840.  A slot's state is ``ssm_work.state_bytes`` an M layer
(4,255,744 B), a token's K/V ``ssm_work.kv_row_bytes`` a * layer (1,024 B).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.lib import hybrid_work, ssm_work

PREFILL_PROGRAMS = "jit_serve_prefill_"


def applies(cfg) -> bool:
    """A model of one-sublayer layers with experts in a latent; False for
    any other, and for a program that has no such fields (the parent of the
    PR that brought this file)."""
    return (bool(getattr(cfg, "one_sublayer", False))
            and bool(getattr(cfg, "moe_latent_size", None))
            and bool(getattr(cfg, "ssm_heads", 0)))


def layer_counts(cfg) -> Dict[str, int]:
    """``{"ssm": M layers, "mlp": E layers, "full": * layers}`` among the
    layers run."""
    run = cfg.layer_pattern[:cfg.num_layers]
    return {kind: run.count(kind) for kind in ("ssm", "mlp", "full")}


def mixer_matmul_params(cfg) -> int:
    """The mixer's in- and out-projection: what a token multiplies through."""
    d, d_ssm = cfg.hidden_size, cfg.ssm_heads * cfg.ssm_head_dim
    return (d * (d_ssm + ssm_work.conv_channels(cfg) + cfg.ssm_heads)
            + d_ssm * d)


def mixer_layer_params(cfg) -> int:
    """An M layer: the two projections, the convolution with its bias, A,
    D, dt's bias, the gated norm and the layer's one norm (109,640,064)."""
    return (mixer_matmul_params(cfg)
            + ssm_work.conv_channels(cfg) * (cfg.ssm_conv + 1)
            + 3 * cfg.ssm_heads + cfg.ssm_heads * cfg.ssm_head_dim
            + cfg.hidden_size)


def attention_matmul_params(cfg) -> int:
    d, hd = cfg.hidden_size, cfg.dims_per_head
    return 2 * d * cfg.num_heads * hd + 2 * d * cfg.kv_heads * hd


def attention_layer_params(cfg) -> int:
    """A * layer: q, k, v, o and its one norm (35,655,680)."""
    return attention_matmul_params(cfg) + cfg.hidden_size


def expert_params(cfg) -> int:
    """One routed expert's two matrices on the latent (5,505,024)."""
    return 2 * cfg.moe_latent_size * (cfg.moe_intermediate_size
                                      or cfg.intermediate_size)


def expert_layer_matmul_params(cfg) -> int:
    """What every token of an E layer multiplies through whatever the router
    chose: the router at its full width, the two latent projections, the
    shared expert (ungated: two matrices of ``moe_shared_experts`` expert
    widths on the model's width)."""
    d = cfg.hidden_size
    f = cfg.moe_intermediate_size or cfg.intermediate_size
    return (d * cfg.num_experts + 2 * d * cfg.moe_latent_size
            + 2 * d * cfg.moe_shared_experts * f)


def expert_layer_dense_params(cfg) -> int:
    """An E layer outside its routed experts: those, the selection bias and
    its one norm (54,530,560)."""
    return (expert_layer_matmul_params(cfg) + cfg.num_experts
            + cfg.hidden_size)


def held_experts(cfg) -> int:
    return cfg.moe_experts_held or cfg.num_experts


def head_params(cfg) -> int:
    """The untied head, read whole, and the final norm (of the embedding a
    token looks up one row)."""
    return cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def held_params(cfg) -> int:
    """Every parameter this chip holds (4,648,163,712 = 9.30 GB; of the
    uncut configuration 120,668,707,840)."""
    n = layer_counts(cfg)
    return (n["ssm"] * mixer_layer_params(cfg)
            + n["full"] * attention_layer_params(cfg)
            + n["mlp"] * (expert_layer_dense_params(cfg)
                          + held_experts(cfg) * expert_params(cfg))
            + cfg.vocab_size * cfg.hidden_size + head_params(cfg))


def decode_tick_work(cfg, experts_touched: float, state_slots: float,
                     kv_live_rows: float, bytes_per_el: int = 2
                     ) -> Dict[str, float]:
    """One decode tick, by what moves it.  ``expert_layer_bytes``: each E
    layer's router, projections and shared expert once and the two matrices
    of each held expert a live row reached (``experts_touched``, summed over
    the layers).  ``mixer_weight_bytes`` / ``attention_weight_bytes`` /
    ``head_bytes``: the M layers', the * layers' and the head's parameters
    once.  ``state_bytes``: every live slot's state of every M layer read
    once and written once.  ``kv_bytes``: the live token rows of the *
    layers (``kv_live_rows``: rows x those layers)."""
    n, b = layer_counts(cfg), float(bytes_per_el)
    return {
        "expert_layer_bytes": b * (
            n["mlp"] * expert_layer_dense_params(cfg)
            + experts_touched * expert_params(cfg)),
        "mixer_weight_bytes": b * n["ssm"] * mixer_layer_params(cfg),
        "attention_weight_bytes": b * n["full"] * attention_layer_params(cfg),
        "head_bytes": b * head_params(cfg),
        "state_bytes": 2.0 * state_slots * n["ssm"] * ssm_work.state_bytes(cfg),
        "kv_bytes": kv_live_rows * ssm_work.kv_row_bytes(cfg, bytes_per_el),
    }


def tick_bytes(cfg, a: Dict[str, Any]) -> Dict[str, float]:
    """:func:`decode_tick_work` of one ``serve.decode`` span's attrs."""
    return decode_tick_work(cfg, a["experts_touched_held"], a["state_slots"],
                            a["kv_live_rows"])


def prefill_flops(cfg, tokens: float, pairs_held: float) -> float:
    """One prompt of ``tokens`` real tokens that starts its slot: two
    operations a dense matmul parameter a token (the in- and out-projections
    of the M layers; q, k, v, o; the router, the latent's two products and
    the shared expert of the E layers; norm scales, A, D and dt's bias
    multiply nothing), two an expert parameter a (token, expert) pair whose
    expert is held here (``pairs_held``, over the layers: 22 a token are
    chosen, about a quarter of them held), the head over ONE position,
    causal attention's two products in the * layers, and the recurrence's
    and convolution's operations a token in the M layers
    (``ssm_work.recurrence_ops``: five a state element)."""
    n = layer_counts(cfg)
    matmul = (n["ssm"] * mixer_matmul_params(cfg)
              + n["full"] * attention_matmul_params(cfg)
              + n["mlp"] * expert_layer_matmul_params(cfg))
    return (2.0 * tokens * matmul + 2.0 * pairs_held * expert_params(cfg)
            + 2.0 * cfg.vocab_size * cfg.hidden_size
            + tokens * (tokens + 1) / 2 * n["full"]
            * ssm_work.attention_ops_per_row(cfg)
            + tokens * n["ssm"] * ssm_work.recurrence_ops(cfg))


def _calls(record, span: str, attr: str) -> Optional[List[Dict[str, Any]]]:
    cfg = record.get("serve", {}).get("cfg")
    if cfg is None or not applies(cfg):
        return None
    return hybrid_work.calls(record, span, attr) or None


def decode_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.decode`` spans that carry the
    per-kind layer counts, the latent's rows and the held experts touched (a
    backlog's drain is left out); None where the model is another or the
    program has no such counters."""
    calls = _calls(record, "serve.decode", "moe_latent_rows") or []
    return [a for a in calls if "experts_touched_held" in a
            and "state_slots" in a and "kv_live_rows" in a] or None


def prefill_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.prefill`` spans that carry
    ``moe_latent_rows`` and ``pairs_held``, in the order they were opened;
    None as above."""
    calls = _calls(record, "serve.prefill", "moe_latent_rows") or []
    return [a for a in calls if "pairs_held" in a] or None
