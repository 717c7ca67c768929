"""Bytes and operations of a decode tick of a latent-attention (MLA) model
with shared experts beside a held share of routed ones, from shapes and the
program's counters (``live_rows`` and the ``moe_*`` attrs of the
``serve.decode`` spans).  The yardstick, like ``flops.py``, ``moe_work.py``
and ``hybrid_work.py``: what the algorithm needs, never what the compiled
program executes.

A latent row is one token's cache row of one layer: the normed
``kv_lora_rank``-wide latent and the ``rotary_dim``-wide rotated key row
every head shares (576 bf16 = 1,152 B for Kanana-2).  Through the absorbed
path a query of ``num_heads`` heads meets each live row twice: its score
(``kv_lora_rank + rotary_dim`` wide) and its share of the value sum
(``kv_lora_rank`` wide).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.lib import hybrid_work


def is_latent(cfg) -> bool:
    """False for any other model, and for a program that has no such
    field (the parent of the PR that brought this file)."""
    return bool(getattr(cfg, "kv_lora_rank", None))


def latent_row_bytes(cfg, bytes_per_el: int = 2) -> float:
    """One token's cache row of ONE layer."""
    return float((cfg.kv_lora_rank + cfg.rotary_dim) * bytes_per_el)


def absorbed_ops_per_row(cfg) -> float:
    """Operations one live latent row of one layer costs a decode token:
    every head's score against the whole row, and its weight times the
    latent (2 x heads x (576 + 512) = 69,632 for Kanana-2)."""
    return 2.0 * cfg.num_heads * (2 * cfg.kv_lora_rank + cfg.rotary_dim)


def expert_params(cfg) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg.hidden_size * (cfg.moe_intermediate_size
                                  or cfg.intermediate_size)


def non_routed_params(cfg) -> int:
    """Every parameter a tick streams whatever the routers do: each layer's
    attention (W_q, W_kva and its norm, W_kvb, W_o) and norms, the leading
    dense MLPs, and in an expert layer the router at its full width, its
    bias and the shared experts' MLP; the final norm and the untied head
    (of the embedding a tick looks up one row a slot)."""
    d, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.dims_per_head
    r, rd, vd = cfg.kv_lora_rank, cfg.rotary_dim, cfg.v_head_dim
    attn = (d * nh * hd + d * (r + rd) + r + r * nh * (hd - rd + vd)
            + nh * vd * d + 2 * d)
    dense = 3 * d * cfg.intermediate_size
    moe = (d * cfg.num_experts
           + (cfg.num_experts if cfg.moe_select_bias else 0)
           + cfg.moe_shared_experts * expert_params(cfg))
    n_moe = cfg.num_layers - cfg.dense_layers
    return (cfg.num_layers * attn + cfg.dense_layers * dense + n_moe * moe
            + cfg.vocab_size * d + d)


def decode_tick_work(cfg, live_rows: float, experts_touched: float,
                     live_tokens: float, local_pairs: float,
                     bytes_per_el: int = 2) -> Dict[str, float]:
    """One decode tick: ``live_rows`` token rows the live slots hold (a
    layer), ``experts_touched`` held experts with a live row (summed over
    layers), ``live_tokens`` slots with a token, ``local_pairs`` (token,
    expert) pairs that landed on a held expert (summed over layers).
    ``latent_bytes``: the live rows of every layer.  ``weight_bytes``: what
    lies outside the routed experts once, and each touched expert's
    matrices.  ``flops``: the absorbed products over the live rows, and two
    a parameter a token passes through."""
    latent = live_rows * cfg.num_layers * latent_row_bytes(cfg, bytes_per_el)
    weights = (non_routed_params(cfg) + experts_touched * expert_params(cfg)
               ) * bytes_per_el
    flops = (live_rows * cfg.num_layers * absorbed_ops_per_row(cfg)
             + 2.0 * live_tokens * non_routed_params(cfg)
             + 2.0 * local_pairs * expert_params(cfg))
    return {"latent_bytes": float(latent), "weight_bytes": float(weights),
            "flops": float(flops)}


def decode_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.decode`` spans that carry both the
    row and the expert counters (a backlog's drain is left out); None where
    the model is not a latent one or the program has no such counters."""
    cfg = record.get("serve", {}).get("cfg")
    if cfg is None or not is_latent(cfg):
        return None
    calls = [a for a in hybrid_work.calls(record, "serve.decode", "live_rows")
             if "moe_experts_touched" in a]
    return calls or None


def tick_work(cfg, a: Dict[str, Any]) -> Dict[str, float]:
    """:func:`decode_tick_work` of one ``serve.decode`` span's attrs."""
    layers = cfg.num_layers - cfg.dense_layers
    return decode_tick_work(
        cfg, a["live_rows"], a["moe_experts_touched"],
        a["moe_pairs"] / (cfg.moe_top_k * layers), a["moe_local_pairs"])
