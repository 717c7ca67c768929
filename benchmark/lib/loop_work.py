"""Bytes and operations of a decode tick of a looped model (one stack of
layers run ``loop_passes`` times a token, every pass with keys and values of
its own), from shapes and the program's counters (``kv_bytes``, ``passes``,
``live_rows``, ``own_slots`` of the ``serve.decode`` spans).  The yardstick,
like ``flops.py``, ``moe_work.py``, ``hybrid_work.py``, ``mla_work.py`` and
``ssm_work.py``: what the algorithm needs, never what the compiled program
executes, and the same whatever implements the passes.

The stack does not fit on-chip memory (4.93 GB for Ouro-2.6B), so a tick
streams it once a PASS: ``passes x`` the stack's bytes, beside the head once
and the live rows of a cache ``passes x layers`` deep.  Every other reader's
work counts a layer's weights once a token (``flops.decode_tick_bytes``).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.lib import hybrid_work


def is_looped(cfg) -> bool:
    """False for any other model, and for a program that has no such field
    (the parent of the PR that brought this file)."""
    return getattr(cfg, "loop_passes", 1) > 1


def layer_params(cfg) -> int:
    """One layer: q, k, v, o, the gated MLP, four norms (51,388,416)."""
    d, hd = cfg.hidden_size, cfg.dims_per_head
    attn = 2 * d * cfg.num_heads * hd + 2 * d * cfg.kv_heads * hd
    return attn + 3 * d * cfg.intermediate_size + 4 * d


def stack_params(cfg) -> int:
    """What ONE pass streams: the layers and the final norm that ends it."""
    return cfg.num_layers * layer_params(cfg) + cfg.hidden_size


def head_params(cfg) -> int:
    """The untied head, once a tick (of the embedding a token looks up one
    row)."""
    return cfg.vocab_size * cfg.hidden_size


def kv_token_bytes(cfg, bytes_per_el: int = 2) -> int:
    """One token's keys and values over every layer of every pass
    (1,572,864 B): what the program's ``kv_bytes`` counts a live row."""
    return (cfg.loop_passes * cfg.num_layers * 2 * cfg.kv_heads
            * cfg.dims_per_head * bytes_per_el)


def attention_ops_per_row(cfg) -> float:
    """Operations one live K/V row of one layer of one pass costs a query
    token: its score and its share of the value sum, every head (8,192)."""
    return 4.0 * cfg.num_heads * cfg.dims_per_head


def decode_tick_work(cfg, kv_bytes: float, live_rows: float,
                     live_slots: float) -> Dict[str, float]:
    """One decode tick: ``kv_bytes`` as the program counted them (the rows
    the live slots hold x :func:`kv_token_bytes`), ``live_rows`` those rows
    (a layer's), ``live_slots`` slots with a token.  ``weight_bytes``: the
    stack once a pass and the head once.  ``flops``: two a parameter a token
    a pass, the head's, and attention's over the live rows of every layer
    of every pass."""
    R = cfg.loop_passes
    return {
        "kv_bytes": float(kv_bytes),
        "weight_bytes": 2.0 * (R * stack_params(cfg) + head_params(cfg)),
        "flops": (2.0 * live_slots * (R * stack_params(cfg)
                                      + head_params(cfg))
                  + live_rows * R * cfg.num_layers
                  * attention_ops_per_row(cfg)),
    }


def decode_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.decode`` spans that carry
    ``kv_bytes`` (a backlog's drain is left out); None where the model is
    not looped or the program has no such counter."""
    cfg = record.get("serve", {}).get("cfg")
    if cfg is None or not is_looped(cfg):
        return None
    calls = hybrid_work.calls(record, "serve.decode", "kv_bytes")
    return [a for a in calls if "live_rows" in a] or None


def tick_work(cfg, a: Dict[str, Any]) -> Dict[str, float]:
    """:func:`decode_tick_work` of one ``serve.decode`` span's attrs."""
    return decode_tick_work(cfg, a["kv_bytes"], a["live_rows"],
                            a["own_slots"])
