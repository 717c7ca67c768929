"""Bytes and operations of a dropless expert layer, from shapes and the
program's counters (the ``moe_*`` attrs of the ``serve.decode`` and
``serve.prefill`` spans).  The yardstick, like ``flops.py``: what the
algorithm needs, never what the compiled program executes.

A row is one (token, expert) assignment.  A live row passes through its
expert's three matrices (gate and up ``[d, f]``, down ``[f, d]``), and an
expert's matrices have to be read once by a call in which any live row
reached it, however many rows it has.
"""
from __future__ import annotations

from typing import Dict

from benchmark.lib import flops


def expert_bytes(cfg, bytes_per_el: int = 2) -> float:
    """One expert's three matrices."""
    return float(3 * cfg.hidden_size * cfg.intermediate_size * bytes_per_el)


def non_expert_weight_bytes(cfg, bytes_per_el: int = 2) -> float:
    """``flops.weight_bytes`` less every expert's matrices: what a decode
    tick streams whatever the router does (projections, router, norms,
    LM head)."""
    return (flops.weight_bytes(cfg, bytes_per_el)
            - cfg.num_layers * cfg.num_experts * expert_bytes(cfg, bytes_per_el))


def expert_matmul_work(cfg, live_rows: float, experts_touched: float,
                       bytes_per_el: int = 2) -> Dict[str, float]:
    """The expert matmuls of one call, summed over its layers: ``live_rows``
    (token, expert) rows over all layers, ``experts_touched`` experts with
    at least one live row over all layers.  Operations: 2·d·f for each of
    the three products of each row.  Bytes: each touched expert's matrices
    once, each row's input read and output written once (the gate and up
    products' intermediates are left out: a lower bound)."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    return {"flops": live_rows * 3 * 2.0 * d * f,
            "bytes": (experts_touched * expert_bytes(cfg, bytes_per_el)
                      + live_rows * 2 * d * bytes_per_el)}


def moe_decode_tick_bytes(cfg, experts_touched: float,
                          live_tokens: float) -> float:
    """What one decode tick of an MoE model must move: the non-expert
    weights once, the matrices of the experts its live rows touch
    (``experts_touched`` summed over layers), and the K/V of the tokens the
    active slots hold."""
    return (non_expert_weight_bytes(cfg)
            + experts_touched * expert_bytes(cfg)
            + live_tokens * flops.kv_bytes_per_token(cfg))


# ---- what the readers share -------------------------------------------------

DECODE_PROGRAM = "jit_serve_decode"
# On a TPU ``lax.ragged_dot`` compiles to Mosaic custom calls, named so in
# the chip's op list (PERF.md, PR 26): a layer's three grouped products are
# ``ragged-dot-none``, ``ragged-dot-none.1`` and ``.2``, and they share one
# ``ragged-dot-metadata`` (group offsets and tile visits from the group
# sizes).  All four are the expert matmuls' time.
EXPERT_MATMUL_OPS = ("ragged-dot",)


def moe_calls(record, span_name: str):
    """The attrs of the ``span_name`` spans that carry the program's expert
    counters, those opened inside the measured window where the kind says
    when it ended (``serve["t_end"]``: a backlog's drain, in which the slots
    empty one by one, is left out); [] where the program has none."""
    t_end = record.get("serve", {}).get("t_end", float("inf"))
    return [s.attrs for s in record.get("spans", [])
            if s.name == span_name and s.attrs and "moe_rows" in s.attrs
            and s.t0 <= t_end]


def program_device_s(trace, program: str = DECODE_PROGRAM):
    """(device seconds, invocations) of the traced modules called
    ``program``."""
    durs = [m[1] for m in trace["modules"] if m[2] == program]
    return sum(durs) * 1e-9, len(durs)


def expert_matmul_device_s(trace, program: str = DECODE_PROGRAM) -> float:
    """Device seconds of the expert matmuls' ops inside ``program``.  A
    ``per_op_s`` key reads ``<module>@<span>:<op name> <opcode> <result>``."""
    total = 0.0
    for label, s in trace["per_op_s"].items():
        module, _, op = label.partition(":")
        if module.split("@", 1)[0] == program and op.startswith(EXPERT_MATMUL_OPS):
            total += s
    return total
