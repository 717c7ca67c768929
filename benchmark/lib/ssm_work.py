"""Bytes and operations of a decode tick and of a prompt's prefill of a
model with state-space layers beside attention (a Mamba-2 mixer and
grouped-query attention in every block), from shapes and the program's
counters (``live_rows``, ``state_slots`` of the ``serve.decode`` spans,
``tokens`` of the ``serve.prefill`` spans).  The yardstick, like
``flops.py``, ``moe_work.py``, ``hybrid_work.py`` and ``mla_work.py``: what
the algorithm needs, never what the compiled program executes.

A slot's state is one ``[heads, head_dim, state]`` float32 tensor a layer
and the convolution's tail of ``taps - 1`` inputs (4,225,024 B for
Falcon-H1-34B); a tick reads it once and writes it once for every live
slot.  The recurrence costs five operations a state element a token (decay,
the outer product's multiply and add, the read's multiply and add); the
chunked form a prompt runs does more arithmetic to reach the matrix unit,
which is the program's choice and is not counted.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.lib import hybrid_work


def is_ssm(cfg) -> bool:
    """False for any other model, and for a program that has no such field
    (the parent of the PR that brought this file)."""
    return bool(getattr(cfg, "ssm_heads", 0))


def conv_channels(cfg) -> int:
    """x, B and C together (5,120)."""
    return (cfg.ssm_heads * cfg.ssm_head_dim
            + 2 * cfg.ssm_groups * cfg.ssm_state)


def state_bytes(cfg) -> float:
    """One slot's state and convolution tail of ONE layer: float32 state,
    bfloat16 tail."""
    return float(cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
                 + (cfg.ssm_conv - 1) * conv_channels(cfg) * 2)


def kv_row_bytes(cfg, bytes_per_el: int = 2) -> float:
    """One token's K and V of ONE layer (2,048 B)."""
    return float(2 * cfg.kv_heads * cfg.dims_per_head * bytes_per_el)


def layer_params(cfg) -> int:
    """One block: attention, the mixer (in- and out-projection, the
    convolution with its bias, A, D, dt's bias, the gated norm), the gated
    MLP, two norms (430.12 M)."""
    d, hd = cfg.hidden_size, cfg.dims_per_head
    d_ssm, conv = cfg.ssm_heads * cfg.ssm_head_dim, conv_channels(cfg)
    attn = 2 * d * cfg.num_heads * hd + 2 * d * cfg.kv_heads * hd
    mixer = (d * (d_ssm + conv + cfg.ssm_heads) + d_ssm * d
             + conv * (cfg.ssm_conv + 1) + 3 * cfg.ssm_heads + d_ssm)
    return attn + mixer + 3 * d * cfg.intermediate_size + 2 * d


def streamed_params(cfg) -> int:
    """Every parameter a program streams whatever its tokens: the layers,
    the final norm and the untied head (of the embedding a token looks up
    one row)."""
    return (cfg.num_layers * layer_params(cfg) + cfg.hidden_size
            + cfg.vocab_size * cfg.hidden_size)


def recurrence_ops(cfg) -> float:
    """Operations one token costs one layer's recurrence and convolution."""
    return float(5 * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
                 + 2 * cfg.ssm_conv * conv_channels(cfg))


def attention_ops_per_row(cfg) -> float:
    """Operations one live K/V row of one layer costs a query token: its
    score and its share of the value sum, every query head (10,240)."""
    return 4.0 * cfg.num_heads * cfg.dims_per_head


def decode_tick_work(cfg, live_rows: float, live_slots: float
                     ) -> Dict[str, float]:
    """One decode tick: ``live_rows`` token rows the live slots hold (a
    layer), ``live_slots`` slots with a token.  ``state_bytes``: each live
    slot's state of every layer read once and written once.  ``kv_bytes``:
    the live rows of every layer.  ``weight_bytes``: the streamed
    parameters once.  ``flops``: two a parameter a token, the recurrence's,
    and attention's over the live rows."""
    L = cfg.num_layers
    return {
        "state_bytes": 2.0 * live_slots * L * state_bytes(cfg),
        "kv_bytes": live_rows * L * kv_row_bytes(cfg),
        "weight_bytes": 2.0 * streamed_params(cfg),
        "flops": (2.0 * live_slots * streamed_params(cfg)
                  + live_slots * L * recurrence_ops(cfg)
                  + live_rows * L * attention_ops_per_row(cfg)),
    }


def prefill_work(cfg, tokens: float) -> Dict[str, float]:
    """One prompt of ``tokens`` real tokens that starts its slot: the
    streamed parameters once, the slot's state written once (it starts from
    zeros, so nothing is read), the prompt's K/V rows written and read once;
    two operations a layer parameter a token, the head over ONE position,
    the recurrence's a token, causal attention over the prompt."""
    L = cfg.num_layers
    head = cfg.vocab_size * cfg.hidden_size
    return {
        "bytes": (2.0 * streamed_params(cfg) + L * state_bytes(cfg)
                  + 2.0 * tokens * L * kv_row_bytes(cfg)
                  + 2.0 * tokens * cfg.hidden_size),
        "flops": (2.0 * tokens * L * layer_params(cfg) + 2.0 * head
                  + tokens * L * recurrence_ops(cfg)
                  + tokens * (tokens + 1) / 2 * L
                  * attention_ops_per_row(cfg)),
    }


def _calls(record, span: str, attr: str) -> Optional[List[Dict[str, Any]]]:
    cfg = record.get("serve", {}).get("cfg")
    if cfg is None or not is_ssm(cfg):
        return None
    return hybrid_work.calls(record, span, attr) or None


def decode_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.decode`` spans that carry
    ``state_slots`` (a backlog's drain is left out); None where the model
    has no state-space layers or the program no such counter."""
    calls = _calls(record, "serve.decode", "state_slots") or []
    return [a for a in calls if "live_rows" in a] or None


def prefill_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.prefill`` spans that carry
    ``scan_chunks``, in the order they were opened; None as above."""
    return _calls(record, "serve.prefill", "scan_chunks")


def tick_work(cfg, a: Dict[str, Any]) -> Dict[str, float]:
    """:func:`decode_tick_work` of one ``serve.decode`` span's attrs."""
    return decode_tick_work(cfg, a["live_rows"], a["state_slots"])
