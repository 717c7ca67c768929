"""Operations and bytes computed from shapes, and the table of peaks.

These are the yardstick: what the algorithm needs, not what the compiled
program executes.  ``bench.py``'s ``model_flops_per_token`` is the origin of
the training arithmetic (6·N + 12·L·d·S); here the causal half is stated and
the parameter count is of the matrices that multiply, LM head included.
Recomputed (remat) operations are never counted.
"""
from __future__ import annotations

import json
import os
from typing import Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; a device not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; add it with its source")
    return table[device_kind]


def matmul_params(cfg) -> int:
    """Parameters of the weight matrices a token multiplies through: q, k,
    v, o and the MLP of every layer, and the LM head (tied or not, the
    product is made).  Embedding rows, biases and norms multiply nothing."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    hd, nh, nkv = cfg.dims_per_head, cfg.num_heads, cfg.kv_heads
    attn = d * nh * hd * 2 + d * nkv * hd * 2
    mlp = (3 if cfg.activation == "swiglu" else 2) * d * f
    return cfg.num_layers * (attn + mlp) + d * cfg.vocab_size


def train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter, plus attention's two
    S×S products, 12·L·(heads·head_dim)·S for the full square, halved
    because a causal model needs only the lower triangle."""
    attn = 12 * cfg.num_layers * cfg.num_heads * cfg.dims_per_head * seq_len
    return 6 * matmul_params(cfg) + attn * 0.5


def mfu(tokens_per_s_chip: float, cfg, seq_len: int, device_kind: str) -> float:
    """Model-FLOP/s utilisation of one chip, 0..1."""
    return (tokens_per_s_chip * train_flops_per_token(cfg, seq_len)
            / peaks(device_kind)["bf16_flops_per_s"])


# ---- flash attention kernels ------------------------------------------------
# One S×S×head_dim product over the causal lower triangle, batch B, H heads.

def _causal_product_flops(B: int, H: int, S: int, hd: int) -> float:
    return 2.0 * B * H * S * S * hd * 0.5


def flash_step_work(B: int, H: int, S: int, hd: int, layers: int,
                    remat: bool, bytes_per_el: int = 2) -> Dict[str, float]:
    """Operations and HBM bytes the flash kernels of ONE training step need.

    The forward makes 2 products (QK^T, PV) and the backward 5 (QK^T again,
    dO·V^T, P^T·dO, dS·K, dS^T·Q): the fewest any flash backward makes.
    This repo's backward is two kernels that each redo QK^T and dO·V^T (7
    products executed); the two extra are the kernel's overhead, not work.
    Under full remat the forward kernel runs twice per layer, and both runs
    are device time of the kernel, so both are counted as invocations.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o,
    dO and writes dq, dk, dv (the [B,H,S] row statistics are ~1/hd of that
    and left out)."""
    prod = _causal_product_flops(B, H, S, hd)
    tensor = float(B * S * H * hd * bytes_per_el)
    n_fwd = layers * (2 if remat else 1)
    return {"flops": n_fwd * 2 * prod + layers * 5 * prod,
            "bytes": n_fwd * 4 * tensor + layers * 8 * tensor,
            "forward_invocations": n_fwd, "backward_invocations": layers}


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
    """(least seconds, which bound) on one chip."""
    pk = peaks(device_kind)
    tf, tb = flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")


# ---- paged decode -----------------------------------------------------------

def weight_bytes(cfg, bytes_per_el: int = 2) -> float:
    """Bytes of every parameter a decode tick must stream: all of them but
    the embedding rows it does not look up (a tied embedding is read whole
    as the LM head; learned positions are a lookup)."""
    total = cfg.param_count
    if not cfg.tie_embeddings:
        total -= cfg.vocab_size * cfg.hidden_size      # input embedding rows
    if cfg.position == "learned":
        total -= cfg.max_seq_len * cfg.hidden_size
    return float(total * bytes_per_el)


def kv_bytes_per_token(cfg, bytes_per_el: int = 2) -> float:
    return float(2 * cfg.num_layers * cfg.kv_heads * cfg.dims_per_head
                 * bytes_per_el)


def decode_tick_bytes(cfg, live_tokens: float) -> float:
    """What one decode tick must move: the weights once, and the K/V of the
    tokens the active slots hold (``live_tokens`` summed over slots)."""
    return weight_bytes(cfg) + live_tokens * kv_bytes_per_token(cfg)
