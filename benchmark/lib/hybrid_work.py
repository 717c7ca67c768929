"""Bytes a decode tick of a model with two kinds of attention layer and a
held share of its experts must move, from shapes and the program's counters
(the ``kv_*`` and ``moe_*`` attrs of the ``serve.decode`` spans).  The
yardstick, like ``flops.py`` and ``moe_work.py``: what the algorithm needs,
never what the compiled program executes.

A K/V *head row* is one token's key and value of one KV head of one layer
(``head_dim + v_head_dim`` elements): the unit of the ``kv_rows_*`` and
``kv_live_rows_*`` attrs, so that a window layer's 8 heads and a full
layer's 4 add up in bytes.
"""
from __future__ import annotations

from typing import Any, Dict, List


def head_row_bytes(cfg, bytes_per_el: int = 2) -> float:
    """One K/V head row: a key and a value of their own widths."""
    return float((cfg.dims_per_head + (cfg.v_head_dim or cfg.dims_per_head))
                 * bytes_per_el)


def expert_bytes(cfg, bytes_per_el: int = 2) -> float:
    """One expert's three matrices, at the experts' own width."""
    return float(3 * cfg.hidden_size * (cfg.moe_intermediate_size
                                        or cfg.intermediate_size)
                 * bytes_per_el)


def _attention_params(cfg, kv_heads: int) -> int:
    d, hd = cfg.hidden_size, cfg.dims_per_head
    vd = cfg.v_head_dim or hd
    return (d * cfg.num_heads * hd + d * kv_heads * (hd + vd)
            + cfg.num_heads * vd * d)


def non_expert_weight_bytes(cfg, bytes_per_el: int = 2) -> float:
    """What a tick streams whatever the routers do: every layer's attention
    projections at its kind's KV heads, the leading dense MLPs, the routers
    at their full width, the norms and sinks, and the untied head (of the
    embedding a tick looks up one row a slot)."""
    d = cfg.hidden_size
    total = cfg.vocab_size * d + d          # head, final norm
    for i, kind in enumerate(cfg.layer_pattern[:cfg.num_layers]):
        kv = (cfg.window_kv_heads or cfg.kv_heads) if kind == "window" \
            else cfg.kv_heads
        total += _attention_params(cfg, kv) + 2 * d
        if kind == "window" and cfg.window_attn_sink:
            total += cfg.num_heads
        if i < cfg.dense_layers:
            total += 3 * d * cfg.intermediate_size
        else:
            total += d * cfg.num_experts + (cfg.num_experts
                                            if cfg.moe_select_bias else 0)
    return float(total * bytes_per_el)


def decode_tick_bytes(cfg, experts_touched: float,
                      live_head_rows: float) -> float:
    """One decode tick: the non-expert weights once, the three matrices of
    each held expert a live row reached (summed over layers), and the live
    K/V head rows of both kinds."""
    return (non_expert_weight_bytes(cfg)
            + experts_touched * expert_bytes(cfg)
            + live_head_rows * head_row_bytes(cfg))


def calls(record, span_name: str, attr: str) -> List[Dict[str, Any]]:
    """The attrs of the ``span_name`` spans that carry ``attr``, those
    opened inside the measured window (a backlog's drain is left out); []
    where the program has no such counter."""
    t_end = record.get("serve", {}).get("t_end", float("inf"))
    return [s.attrs for s in record.get("spans", [])
            if s.name == span_name and s.attrs and attr in s.attrs
            and s.t0 <= t_end]
