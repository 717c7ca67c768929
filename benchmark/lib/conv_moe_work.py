"""Bytes and operations of a decode tick and of a prompt's prefill of a
model whose layers are EITHER a gated short convolution OR attention, the
leading ones before a dense MLP and the rest before an expert layer of which
every expert is held (LFM2-8B-A1B), from shapes and the program's counters
(``state_slots``, ``kv_live_rows``, ``moe_experts_touched`` of the
``serve.decode`` spans; ``tokens``, ``pairs_held`` of the ``serve.prefill``
spans).  The yardstick, like ``flops.py``, ``moe_work.py`` and
``ssm_moe_work.py``: what the algorithm needs, never what the compiled
program executes.

At the published widths a conv layer's operator is 16.78 M parameters (W_in
2,048 x 6,144 = 12.58 M, W_out 2,048 x 2,048 = 4.19 M, 3 x 2,048 taps), an
attention layer's 10.49 M (W_q and W_o 4.19 M each, W_k and W_v 1.05 M each,
two scales of 64), a dense MLP 44.04 M, one expert 11.01 M, a router 65,536
+ 32 biases, the tied embedding 134.22 M.  A slot's state is the
convolution's tail, 2 rows of 2,048 bf16 = 8,192 B a conv layer; a token's
K/V 2 x 8 x 64 x 2 B = 2,048 B an attention layer.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import hybrid_work


def applies(cfg) -> bool:
    """A model with "conv" entries in its ``layer_pattern`` and experts;
    False for any other, and for a program that has no such kind of layer."""
    pattern = getattr(cfg, "layer_pattern", None) or ()
    return ("conv" in pattern[:cfg.num_layers]
            and bool(getattr(cfg, "conv_taps", 0))
            and isinstance(cfg.num_experts, int) and cfg.num_experts > 1)


def layer_counts(cfg) -> Tuple[int, int, int, int]:
    """``(conv, attention, dense, expert)`` layers among the layers run."""
    run = cfg.layer_pattern[:cfg.num_layers]
    dense = min(cfg.dense_layers, len(run))
    conv = run.count("conv")
    return conv, len(run) - conv, dense, len(run) - dense


def conv_matmul_params(cfg) -> int:
    """The operator's in- and out-projection (16,777,216)."""
    return 4 * cfg.hidden_size * cfg.hidden_size


def conv_params(cfg) -> int:
    """The two projections and the taps (16,783,360)."""
    return conv_matmul_params(cfg) + cfg.conv_taps * cfg.hidden_size


def attention_matmul_params(cfg) -> int:
    """q, k, v and o (10,485,760)."""
    d, hd = cfg.hidden_size, cfg.dims_per_head
    return 2 * d * cfg.num_heads * hd + 2 * d * cfg.kv_heads * hd


def attention_params(cfg) -> int:
    """The four projections and the two scales of the QK-norm by head."""
    return attention_matmul_params(cfg) + 2 * cfg.dims_per_head


def dense_mlp_params(cfg) -> int:
    """A leading layer's gated MLP (44,040,192)."""
    return 3 * cfg.hidden_size * cfg.intermediate_size


def expert_params(cfg) -> int:
    """One routed expert's three matrices (11,010,048)."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def router_params(cfg) -> int:
    """The router and the selection bias (65,568)."""
    return cfg.hidden_size * cfg.num_experts + cfg.num_experts


def head_params(cfg) -> int:
    """The tied embedding, read whole as the head (134,217,728), and the
    final norm."""
    return cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def dense_params(cfg) -> int:
    """Every parameter a tick streams whatever its routers chose: the
    operators, the leading dense MLPs, the routers, the norms, the head
    (438.8 M at 14 layers)."""
    n_conv, n_attn, n_dense, n_moe = layer_counts(cfg)
    return (n_conv * conv_params(cfg) + n_attn * attention_params(cfg)
            + n_dense * dense_mlp_params(cfg) + n_moe * router_params(cfg)
            + (n_conv + n_attn) * 2 * cfg.hidden_size + head_params(cfg))


def held_params(cfg) -> int:
    """Every parameter the chip holds (4,667,077,376 at 14 layers = 9.33
    GB in bfloat16; 8,339,930,560 at the published 24)."""
    return dense_params(cfg) + (layer_counts(cfg)[3] * cfg.num_experts
                                * expert_params(cfg))


def tail_bytes(cfg, bytes_per_el: int = 2) -> int:
    """A slot's tail a conv layer (8,192 B)."""
    return (cfg.conv_taps - 1) * cfg.hidden_size * bytes_per_el


def kv_row_bytes(cfg, bytes_per_el: int = 2) -> int:
    """A token's K and V an attention layer (2,048 B)."""
    return 2 * cfg.kv_heads * cfg.dims_per_head * bytes_per_el


def decode_tick_work(cfg, experts_touched: float, state_slots: float,
                     kv_live_rows: float, bytes_per_el: int = 2
                     ) -> Dict[str, float]:
    """One decode tick.  ``dense_bytes``: the operators, dense MLPs, routers,
    norms and head once.  ``expert_bytes``: the three matrices of each
    expert a live row reached (``experts_touched``, summed over the
    layers).  ``tail_bytes``: every live slot's tail of every conv layer
    read once and written once.  ``kv_bytes``: the live token rows of the
    attention layers (``kv_live_rows``: rows x those layers)."""
    n_conv = layer_counts(cfg)[0]
    return {
        "dense_bytes": float(bytes_per_el) * dense_params(cfg),
        "expert_bytes": (float(bytes_per_el) * experts_touched
                         * expert_params(cfg)),
        "tail_bytes": (2.0 * state_slots * n_conv
                       * tail_bytes(cfg, bytes_per_el)),
        "kv_bytes": kv_live_rows * kv_row_bytes(cfg, bytes_per_el),
    }


def attention_ops_per_row(cfg) -> float:
    """Operations one live K/V row of one layer costs a query token: its
    score and its share of the value sum, every query head (8,192)."""
    return 4.0 * cfg.num_heads * cfg.dims_per_head


def conv_ops_per_token(cfg) -> float:
    """The two gates and the taps' multiply-adds a token a conv layer."""
    return float((2 + 2 * cfg.conv_taps) * cfg.hidden_size)


def prefill_flops(cfg, tokens: float, pairs: float) -> float:
    """One prompt of ``tokens`` real tokens that starts its slot: two
    operations a matmul parameter a token outside the experts (norm scales
    and taps multiply nothing here), two an expert parameter a (token,
    expert) pair (``pairs``, over the layers), the head over ONE position,
    causal attention's two products in the attention layers, the gates and
    taps in the conv layers."""
    n_conv, n_attn, n_dense, n_moe = layer_counts(cfg)
    d = cfg.hidden_size
    matmul = (n_conv * conv_matmul_params(cfg)
              + n_attn * attention_matmul_params(cfg)
              + n_dense * dense_mlp_params(cfg) + n_moe * d * cfg.num_experts)
    return (2.0 * tokens * matmul + 2.0 * pairs * expert_params(cfg)
            + 2.0 * cfg.vocab_size * d
            + tokens * (tokens + 1) / 2 * n_attn * attention_ops_per_row(cfg)
            + tokens * n_conv * conv_ops_per_token(cfg))


def _calls(record, span: str, attr: str) -> Optional[List[Dict[str, Any]]]:
    cfg = record.get("serve", {}).get("cfg")
    if cfg is None or not applies(cfg):
        return None
    return hybrid_work.calls(record, span, attr) or None


def decode_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.decode`` spans that carry the
    state's and the experts' counters (a backlog's drain is left out); None
    where the model is another or the program has no such counters."""
    calls = _calls(record, "serve.decode", "state_slots") or []
    return [a for a in calls if "moe_experts_touched" in a
            and "kv_live_rows" in a] or None


def prefill_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.prefill`` spans that carry
    ``pairs_held``, in the order they were opened; None as above."""
    return _calls(record, "serve.prefill", "pairs_held")
