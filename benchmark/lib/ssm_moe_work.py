"""Bytes and operations of a decode tick and of a prompt's prefill of a
model whose layers are EITHER a state-space mixer OR attention, with an
expert layer (a held share of the routed experts and a shared expert) behind
every one, from shapes and the program's counters (``state_slots``,
``state_layers``, ``kv_live_rows``, ``experts_touched_held`` of the
``serve.decode`` spans; ``tokens``, ``pairs_held`` of the ``serve.prefill``
spans).  The yardstick, like ``flops.py``, ``moe_work.py`` and
``ssm_work.py``: what the algorithm needs, never what the compiled program
executes.

A mamba layer is the mixer (102.3 M parameters at Granite-4.0-H-Small's
widths), the held experts (36 x 9.44 M), the shared expert (18.9 M), the
router at its full width and two norms: 461.2 M.  An attention layer has
41.9 M of projections in the mixer's place: 400.9 M.  With the vocabulary
slice's tied embedding (205.5 M) ten layers are 4.757 B = 9.51 GB in
bfloat16.  A slot's state is ``ssm_work.state_bytes`` a mamba layer
(4,244,992 B), a token's K/V ``ssm_work.kv_row_bytes`` an attention layer
(4,096 B).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import hybrid_work, ssm_work

PREFILL_PROGRAMS = "jit_serve_prefill_"


def applies(cfg) -> bool:
    """A model with "ssm" entries in its ``layer_pattern`` and experts; False
    for any other, and for a program that has no such kind of layer."""
    pattern = getattr(cfg, "layer_pattern", None) or ()
    return ("ssm" in pattern[:cfg.num_layers]
            and bool(getattr(cfg, "ssm_heads", 0))
            and isinstance(cfg.num_experts, int) and cfg.num_experts > 1)


def layer_counts(cfg) -> Tuple[int, int]:
    """``(mamba layers, attention layers)`` among the layers run."""
    run = cfg.layer_pattern[:cfg.num_layers]
    return run.count("ssm"), len(run) - run.count("ssm")


def mixer_matmul_params(cfg) -> int:
    """The mixer's in- and out-projection: what a token multiplies through."""
    d, d_ssm = cfg.hidden_size, cfg.ssm_heads * cfg.ssm_head_dim
    return (d * (d_ssm + ssm_work.conv_channels(cfg) + cfg.ssm_heads)
            + d_ssm * d)


def mixer_params(cfg) -> int:
    """The two projections, the convolution with its bias, A, D, dt's bias
    and the gated norm (102,286,976)."""
    return (mixer_matmul_params(cfg)
            + ssm_work.conv_channels(cfg) * (cfg.ssm_conv + 1)
            + 3 * cfg.ssm_heads + cfg.ssm_heads * cfg.ssm_head_dim)


def attention_params(cfg) -> int:
    """q, k, v and o (41,943,040)."""
    d, hd = cfg.hidden_size, cfg.dims_per_head
    return 2 * d * cfg.num_heads * hd + 2 * d * cfg.kv_heads * hd


def expert_params(cfg) -> int:
    """One routed expert's three matrices (9,437,184)."""
    return 3 * cfg.hidden_size * (cfg.moe_intermediate_size
                                  or cfg.intermediate_size)


def shared_params(cfg) -> int:
    """The shared expert: one gated MLP of ``moe_shared_experts`` expert
    widths (18,874,368)."""
    return cfg.moe_shared_experts * expert_params(cfg)


def held_experts(cfg) -> int:
    return cfg.moe_experts_held or cfg.num_experts


def dense_layer_params(cfg, kind: str) -> int:
    """What every token of a layer passes through whatever the router does:
    its one mixer, the shared expert, the router at its full width, two
    norms."""
    d = cfg.hidden_size
    return ((mixer_params(cfg) if kind == "ssm" else attention_params(cfg))
            + shared_params(cfg) + d * cfg.num_experts + 2 * d)


def layer_params(cfg, kind: str) -> int:
    """A whole layer as held here (461,203,072 / 400,859,136)."""
    return dense_layer_params(cfg, kind) + held_experts(cfg) * expert_params(cfg)


def head_params(cfg) -> int:
    """The tied embedding, read whole as the head (205,520,896), and the
    final norm."""
    return cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def held_params(cfg) -> int:
    """Every parameter this chip holds (4,757,211,776 = 9.51 GB)."""
    n_ssm, n_attn = layer_counts(cfg)
    return (n_ssm * layer_params(cfg, "ssm") + n_attn * layer_params(cfg, "full")
            + head_params(cfg))


def dense_params(cfg) -> int:
    """``held_params`` less every held expert: what a tick streams whatever
    its routers chose."""
    n_ssm, n_attn = layer_counts(cfg)
    return held_params(cfg) - (n_ssm + n_attn) * held_experts(cfg) * expert_params(cfg)


def decode_tick_work(cfg, experts_touched: float, state_slots: float,
                     kv_live_rows: float, bytes_per_el: int = 2
                     ) -> Dict[str, float]:
    """One decode tick.  ``weight_bytes``: the dense parameters once and the
    three matrices of each held expert a live row reached
    (``experts_touched``, summed over the layers).  ``state_bytes``: every
    live slot's state of every mamba layer read once and written once.
    ``kv_bytes``: the live token rows of the attention layers
    (``kv_live_rows``: rows x those layers)."""
    n_ssm, _ = layer_counts(cfg)
    return {
        "weight_bytes": float(bytes_per_el) * (
            dense_params(cfg) + experts_touched * expert_params(cfg)),
        "state_bytes": 2.0 * state_slots * n_ssm * ssm_work.state_bytes(cfg),
        "kv_bytes": kv_live_rows * ssm_work.kv_row_bytes(cfg, bytes_per_el),
    }


def tick_bytes(cfg, a: Dict[str, Any]) -> Dict[str, float]:
    """:func:`decode_tick_work` of one ``serve.decode`` span's attrs."""
    return decode_tick_work(cfg, a["experts_touched_held"], a["state_slots"],
                            a["kv_live_rows"])


def prefill_flops(cfg, tokens: float, pairs_held: float) -> float:
    """One prompt of ``tokens`` real tokens that starts its slot: two
    operations a dense matmul parameter a token (norm scales, A, D and dt's
    bias multiply nothing), two an expert parameter a (token, expert) pair
    whose expert is held here (``pairs_held``, over the layers), the head
    over ONE position, causal attention's two products in the attention
    layers, and the recurrence's and convolution's operations a token in
    the mamba layers (``ssm_work.recurrence_ops``: five a state element)."""
    n_ssm, n_attn = layer_counts(cfg)
    d = cfg.hidden_size
    every = shared_params(cfg) + d * cfg.num_experts
    matmul = (n_ssm * (mixer_matmul_params(cfg) + every)
              + n_attn * (attention_params(cfg) + every))
    return (2.0 * tokens * matmul + 2.0 * pairs_held * expert_params(cfg)
            + 2.0 * cfg.vocab_size * d
            + tokens * (tokens + 1) / 2 * n_attn
            * ssm_work.attention_ops_per_row(cfg)
            + tokens * n_ssm * ssm_work.recurrence_ops(cfg))


def _calls(record, span: str, attr: str) -> Optional[List[Dict[str, Any]]]:
    cfg = record.get("serve", {}).get("cfg")
    if cfg is None or not applies(cfg):
        return None
    return hybrid_work.calls(record, span, attr) or None


def decode_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.decode`` spans that carry the
    per-kind layer counts and the held experts touched (a backlog's drain is
    left out); None where the model is another or the program has no such
    counters."""
    calls = _calls(record, "serve.decode", "state_layers") or []
    return [a for a in calls if "experts_touched_held" in a
            and "kv_live_rows" in a] or None


def prefill_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.prefill`` spans that carry
    ``pairs_held``, in the order they were opened; None as above."""
    return _calls(record, "serve.prefill", "pairs_held")


def programs_device_s(trace, prefix: str) -> float:
    """Device seconds of the traced modules whose name starts with
    ``prefix``."""
    return sum(m[1] for m in trace["modules"] if m[2].startswith(prefix)) * 1e-9
