"""Bytes and operations of a decode tick and of a prompt's prefill of a
model whose layers are EITHER a gated-delta-rule mixer (a matrix state a head)
OR full attention, a gated MLP behind every one, from shapes and the
program's counters (``state_slots``, ``state_layers``, ``kv_live_rows`` of
the ``serve.decode`` spans; ``tokens`` of the ``serve.prefill`` spans).  The
yardstick, like ``flops.py``, ``ssm_work.py`` and ``ssm_moe_work.py``: what
the algorithm needs, never what the compiled program executes nor what its
layout pads, and the same whichever step the tick holds.

At Olmo-Hybrid-7B's widths a delta layer is 215,570,172 parameters (88.47 M
in the five projections q, k, v, gate and out, 0.23 M in b and a, the taps,
A, dt's bias and the norm by head 0.05 M, the MLP 126.81 M, two norms), an
attention layer 185,809,920 (q, k, v, o 58.98 M, the QK-norm, the MLP, two
norms), the untied head 385.35 M beside an embedding of which a token reads
one row.  Layers 0-15 are 12 + 4 of them: 4,100,788,944 parameters = 8.20 GB
in bfloat16.  A slot's matrix states are 30 x 96 x 192 float32 = 2,211,840 B
a delta layer (26,542,080 B over the twelve), its convolution tail 3 x 11,520
bfloat16 = 69,120 B a layer; a token's K/V 2 x 30 x 128 x 2 B = 15,360 B an
attention layer (61,440 B over the four).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import hybrid_work
from benchmark.lib.moe_work import DECODE_PROGRAM

STEP_OPS = "delta_step"     # the one-pass kernel's ops, by the kernel's name


def applies(cfg) -> bool:
    """A model with "linear" entries in the layers it runs; False for any
    other (Falcon-H1, Granite), and for a program that has no such kind."""
    pattern = getattr(cfg, "layer_pattern", None) or ()
    return ("linear" in pattern[:cfg.num_layers]
            and bool(getattr(cfg, "linear_heads", 0)))


def layer_counts(cfg) -> Tuple[int, int]:
    """``(delta layers, attention layers)`` among the layers run."""
    run = cfg.layer_pattern[:cfg.num_layers]
    return run.count("linear"), len(run) - run.count("linear")


def conv_channels(cfg) -> int:
    """q, k and v together (11,520)."""
    return cfg.linear_heads * (2 * cfg.linear_key_dim + cfg.linear_value_dim)


def state_bytes(cfg) -> int:
    """One slot's matrix states of ONE delta layer, float32, as the
    equations hold them (2,211,840): no row padded."""
    return (cfg.linear_heads * cfg.linear_key_dim * cfg.linear_value_dim * 4)


def tail_bytes(cfg, bytes_per_el: int = 2) -> int:
    """One slot's convolution tail of ONE delta layer (69,120)."""
    return (cfg.linear_conv - 1) * conv_channels(cfg) * bytes_per_el


def kv_row_bytes(cfg, bytes_per_el: int = 2) -> int:
    """One token's K and V of ONE attention layer (15,360)."""
    return 2 * cfg.kv_heads * cfg.dims_per_head * bytes_per_el


def mixer_matmul_params(cfg) -> int:
    """The delta mixer's projections: q, k, v, the gate, b and a, and out
    (88,704,000)."""
    d = cfg.hidden_size
    dv = cfg.linear_heads * cfg.linear_value_dim
    return d * (conv_channels(cfg) + dv + 2 * cfg.linear_heads) + dv * d


def mixer_params(cfg) -> int:
    """The projections, the taps, A, dt's bias and the norm by head
    (88,750,332)."""
    return (mixer_matmul_params(cfg) + cfg.linear_conv * conv_channels(cfg)
            + 2 * cfg.linear_heads + cfg.linear_value_dim)


def attention_matmul_params(cfg) -> int:
    """q, k, v and o (58,982,400)."""
    d, hd = cfg.hidden_size, cfg.dims_per_head
    return 2 * d * cfg.num_heads * hd + 2 * d * cfg.kv_heads * hd


def attention_params(cfg) -> int:
    """The four projections and the QK-norm's two scales (58,990,080)."""
    return (attention_matmul_params(cfg)
            + (cfg.num_heads + cfg.kv_heads) * cfg.dims_per_head)


def mlp_params(cfg) -> int:
    """Gate, up and down (126,812,160)."""
    return 3 * cfg.hidden_size * cfg.intermediate_size


def layer_params(cfg, kind: str) -> int:
    """A whole layer: its one mixer, the MLP, two norms (215,570,172 /
    185,809,920)."""
    return ((mixer_params(cfg) if kind == "linear" else attention_params(cfg))
            + mlp_params(cfg) + 2 * cfg.hidden_size)


def head_params(cfg) -> int:
    """The untied head and the final norm (385,355,520)."""
    return cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def held_params(cfg) -> int:
    """Every parameter the chip holds, the embedding among them
    (4,100,788,944 = 8.20 GB)."""
    n_lin, n_attn = layer_counts(cfg)
    return (n_lin * layer_params(cfg, "linear")
            + n_attn * layer_params(cfg, "full") + head_params(cfg)
            + cfg.vocab_size * cfg.hidden_size)


def streamed_params(cfg) -> int:
    """What a program streams whatever its tokens: the layers, the final
    norm and the head ONCE; of the embedding a token looks up one row."""
    return held_params(cfg) - cfg.vocab_size * cfg.hidden_size


def recurrence_ops(cfg) -> float:
    """Operations one token costs one delta layer's recurrence and
    convolutions: the decay, ``S^T k``, the rank-one update and ``S^T q``,
    seven a state element a head, and the taps."""
    return float(7 * cfg.linear_heads * cfg.linear_key_dim
                 * cfg.linear_value_dim
                 + 2 * cfg.linear_conv * conv_channels(cfg))


def attention_ops_per_row(cfg) -> float:
    """Operations one live K/V row of one attention layer costs a query
    token: its score and its share of the value sum, every head (15,360)."""
    return 4.0 * cfg.num_heads * cfg.dims_per_head


def decode_tick_work(cfg, state_slots: float, kv_live_rows: float,
                     bytes_per_el: int = 2) -> Dict[str, float]:
    """One decode tick of ``state_slots`` live slots.  ``weight_bytes``: the
    streamed parameters once.  ``state_bytes``: every live slot's matrix
    states and tail of every delta layer read once and written once.
    ``kv_bytes``: the live token rows of the attention layers
    (``kv_live_rows``: rows x those layers).  ``flops``: two a streamed
    matmul parameter a token, the recurrence's, attention's over the live
    rows."""
    n_lin, n_attn = layer_counts(cfg)
    matmul = (n_lin * (mixer_matmul_params(cfg) + mlp_params(cfg))
              + n_attn * (attention_matmul_params(cfg) + mlp_params(cfg))
              + cfg.vocab_size * cfg.hidden_size)
    return {
        "weight_bytes": float(bytes_per_el) * streamed_params(cfg),
        "state_bytes": 2.0 * state_slots * n_lin * (
            state_bytes(cfg) + tail_bytes(cfg, bytes_per_el)),
        "kv_bytes": float(kv_live_rows) * kv_row_bytes(cfg, bytes_per_el),
        "flops": (2.0 * state_slots * matmul
                  + state_slots * n_lin * recurrence_ops(cfg)
                  + kv_live_rows * attention_ops_per_row(cfg)),
    }


def tick_bytes(cfg, a: Dict[str, Any]) -> Dict[str, float]:
    """The three byte counts of one ``serve.decode`` span's attrs."""
    work = decode_tick_work(cfg, a["state_slots"], a["kv_live_rows"])
    return {k: v for k, v in work.items() if k.endswith("_bytes")}


def step_bytes(cfg, state_slots: float) -> float:
    """What the delta step itself must move a tick: every live slot's
    matrix states of every delta layer once in and once out."""
    return 2.0 * state_slots * layer_counts(cfg)[0] * state_bytes(cfg)


def prefill_flops(cfg, tokens: float) -> float:
    """One prompt of ``tokens`` real tokens that starts its slot: two
    operations a layer's matmul parameter a token, the head over ONE
    position, causal attention's two products in the attention layers, the
    recurrence's and the taps' operations a token in the delta layers (the
    chunk form does more arithmetic to reach the matrix unit: the program's
    choice, not counted)."""
    n_lin, n_attn = layer_counts(cfg)
    matmul = (n_lin * (mixer_matmul_params(cfg) + mlp_params(cfg))
              + n_attn * (attention_matmul_params(cfg) + mlp_params(cfg)))
    return (2.0 * tokens * matmul + 2.0 * cfg.vocab_size * cfg.hidden_size
            + tokens * (tokens + 1) / 2 * n_attn * attention_ops_per_row(cfg)
            + tokens * n_lin * recurrence_ops(cfg))


def _calls(record, span: str, attr: str) -> Optional[List[Dict[str, Any]]]:
    cfg = record.get("serve", {}).get("cfg")
    if cfg is None or not applies(cfg):
        return None
    return hybrid_work.calls(record, span, attr) or None


def decode_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.decode`` spans that carry the
    per-kind layer counts (a backlog's drain is left out); None where the
    model is another or the program has no such counters."""
    calls = _calls(record, "serve.decode", "state_layers") or []
    return [a for a in calls if "state_slots" in a
            and "kv_live_rows" in a] or None


def prefill_calls(record) -> Optional[List[Dict[str, Any]]]:
    """The attrs of the window's ``serve.prefill`` spans that carry
    ``scan_chunks``, in the order they were opened; None as above."""
    return _calls(record, "serve.prefill", "scan_chunks")


def step_device_s(trace, program: str = DECODE_PROGRAM) -> float:
    """Device seconds of the delta step's kernel inside ``program``.  A
    ``per_op_s`` key reads ``<module>@<span>:<op name> <opcode> <result>``;
    the kernel's ops carry its name (``delta_step.12``)."""
    total = 0.0
    for label, s in trace["per_op_s"].items():
        module, _, op = label.partition(":")
        if (module.split("@", 1)[0] == program
                and op.split(" ", 1)[0].split(".")[0] == STEP_OPS):
            total += s
    return total
