"""Bytes a decode tick must move and operations a prompt's prefill must
compute for a model of window and full attention layers with a gate on
attention's output, four norms a layer, leading dense MLPs and then a held
share of routed experts beside a shared one (Trinity-Large-Preview,
``afmoe``), from shapes and the program's counters (``kv_live_rows_full``,
``kv_live_rows_window``, ``moe_experts_touched`` of the ``serve.decode``
spans; ``tokens``, ``pairs_held`` of the ``serve.prefill`` spans).  The
yardstick, like ``flops.py`` and ``hybrid_work.py``: what the equations
need, never what the compiled program executes.

At the published widths attention is 62,914,816 parameters a layer (q, o
and the gate 3,072 x 6,144 = 18,874,368 each, k and v 3,145,728 each, two
QK-norm scales of 128), the four norms 12,288, a dense MLP 113,246,208, an
expert and the shared expert 28,311,552 each, a router 786,432 + 256
biases, the head 25,024 x 3,072 = 76,873,728 of the share's vocabulary.  A
token's K and V are 2 x 8 x 128 x 2 B = 4,096 B a layer of either kind.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import hybrid_work


def applies(cfg) -> bool:
    """A model with "window" entries in its ``layer_pattern``, a gate on
    attention's output and experts; False for any other, and for a program
    whose configuration has no such field."""
    pattern = getattr(cfg, "layer_pattern", None) or ()
    return ("window" in pattern[:cfg.num_layers]
            and bool(getattr(cfg, "attn_output_gate", False))
            and isinstance(cfg.num_experts, int) and cfg.num_experts > 1)


def layer_counts(cfg) -> Tuple[int, int, int, int]:
    """``(window, full, dense, expert)`` layers among the layers run."""
    run = cfg.layer_pattern[:cfg.num_layers]
    dense = min(cfg.dense_layers, len(run))
    window = run.count("window")
    return window, len(run) - window, dense, len(run) - dense


def attention_matmul_params(cfg) -> int:
    """q, o and the gate, k and v (62,914,560)."""
    d, hd = cfg.hidden_size, cfg.dims_per_head
    return 3 * d * cfg.num_heads * hd + 2 * d * cfg.kv_heads * hd


def attention_params(cfg) -> int:
    """The five projections and the two scales of the QK-norm by head
    (62,914,816)."""
    return attention_matmul_params(cfg) + 2 * cfg.dims_per_head


def norm_params(cfg) -> int:
    """Four norms a layer (12,288)."""
    return 4 * cfg.hidden_size


def dense_mlp_params(cfg) -> int:
    """A leading layer's gated MLP (113,246,208)."""
    return 3 * cfg.hidden_size * cfg.intermediate_size


def expert_params(cfg) -> int:
    """One routed expert's three matrices (28,311,552)."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def shared_params(cfg) -> int:
    """The shared expert(s), one gated MLP (28,311,552)."""
    return cfg.moe_shared_experts * expert_params(cfg)


def router_params(cfg) -> int:
    """The router at its full width and the selection bias (786,688)."""
    return cfg.hidden_size * cfg.num_experts + cfg.num_experts


def head_params(cfg) -> int:
    """The untied head over the share's vocabulary and the final norm."""
    return cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def streamed_params(cfg) -> int:
    """Every parameter a tick streams whatever its routers chose: each
    layer's attention with its gate, the four norms, the leading dense MLPs,
    the routers, the shared expert, the head (of the embedding a tick looks
    up one row a slot)."""
    window, full, dense, moe = layer_counts(cfg)
    return ((window + full) * (attention_params(cfg) + norm_params(cfg))
            + dense * dense_mlp_params(cfg)
            + moe * (router_params(cfg) + shared_params(cfg))
            + head_params(cfg))


def held_params(cfg) -> int:
    """Every parameter the chip holds: what a tick streams, the held
    experts of every expert layer and the embedding's rows (4,144,995,072 at
    the cell's 8 layers, 16 experts and 25,024 ids = 8.29 GB in bfloat16)."""
    held = cfg.moe_experts_held or cfg.num_experts
    return (streamed_params(cfg) + cfg.vocab_size * cfg.hidden_size
            + layer_counts(cfg)[3] * held * expert_params(cfg))


# a token's key and value of one KV head of one layer (512 B): the unit of
# the ``kv_live_rows_*`` attrs, as every model's of two kinds of layer
head_row_bytes = hybrid_work.head_row_bytes


def decode_tick_work(cfg, experts_touched: float, live_head_rows_full: float,
                     live_head_rows_window: float, bytes_per_el: int = 2
                     ) -> Dict[str, float]:
    """One decode tick.  ``streamed_bytes``: :func:`streamed_params` once.
    ``expert_bytes``: the three matrices of each held expert a live row
    reached (``experts_touched``, summed over the layers).
    ``kv_full_bytes`` / ``kv_window_bytes``: the live K/V head rows of the
    full layers (every row a slot holds) and of the window layers (a slot's
    last ``window_size`` rows at most)."""
    row = head_row_bytes(cfg, bytes_per_el)
    return {
        "streamed_bytes": float(bytes_per_el) * streamed_params(cfg),
        "expert_bytes": (float(bytes_per_el) * experts_touched
                         * expert_params(cfg)),
        "kv_full_bytes": live_head_rows_full * row,
        "kv_window_bytes": live_head_rows_window * row,
    }


def attention_ops_per_row(cfg) -> float:
    """Operations one visible K/V row of one layer costs a query token: its
    score and its share of the value sum, every query head (24,576)."""
    return 4.0 * cfg.num_heads * cfg.dims_per_head


def visible_rows(tokens: int, window: Optional[int] = None) -> float:
    """K/V rows the ``tokens`` queries of a prompt that starts its slot see,
    summed: query ``i`` sees ``i + 1``, under a window at most ``window``."""
    if window is None or tokens <= window:
        return tokens * (tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (tokens - window) * float(window)


def prefill_flops(cfg, tokens: int, pairs: float) -> float:
    """One prompt of ``tokens`` real tokens that starts its slot: two
    operations a matmul parameter a token outside the routed experts (the
    gate's projection and the shared expert included; scales multiply
    nothing here), two an expert parameter a (token, held expert) pair
    (``pairs``, over the layers), the head over ONE position, and
    attention's two products over the rows each query sees: all before it
    in a full layer, the window's at most in a window layer."""
    window, full, dense, moe = layer_counts(cfg)
    d = cfg.hidden_size
    matmul = ((window + full) * attention_matmul_params(cfg)
              + dense * dense_mlp_params(cfg)
              + moe * (d * cfg.num_experts + shared_params(cfg)))
    return (2.0 * tokens * matmul + 2.0 * pairs * expert_params(cfg)
            + 2.0 * cfg.vocab_size * d
            + attention_ops_per_row(cfg) * (
                full * visible_rows(tokens)
                + window * visible_rows(tokens, cfg.window_size)))


def _calls(record, span: str, attr: str) -> Optional[List[Dict[str, Any]]]:
    cfg = record.get("serve", {}).get("cfg")
    if cfg is None or not applies(cfg):
        return None
    return hybrid_work.calls(record, span, attr) or None


def decode_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.decode`` spans that carry both
    pools' live rows and the experts' counters (a backlog's drain is left
    out); None where the model is another or the program has no such
    counters."""
    calls = _calls(record, "serve.decode", "kv_live_rows_window") or []
    return [a for a in calls if "moe_experts_touched" in a
            and "kv_live_rows_full" in a] or None


def prefill_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.prefill`` spans that carry
    ``pairs_held``, in the order they were opened; None as above."""
    return _calls(record, "serve.prefill", "pairs_held")
