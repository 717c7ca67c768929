"""Memory reporting (reference ``runtime/utils.py:725,775`` —
``memory_status`` / ``see_memory_usage``: the debugging helpers DeepSpeed
users sprinkle through training scripts).

Device counters come through the accelerator seam
(``get_accelerator().memory_stats()`` — TPU ``memory_stats`` when the
backend exposes them, psutil host stats on the simulated CPU mesh); host
peak RSS comes from the resource module.
"""
from __future__ import annotations

import resource
import sys
from typing import Dict, Optional

from .logging import logger


def program_bytes(mem) -> int:
    """What a compiled program plans to hold on a device, from its
    ``memory_analysis()``: arguments + temporaries + outputs - aliased.  The
    ONE formula wherever a program is held against a memory budget (the
    engine's checkpoint-policy resolver, the autotuner's prune and measure)."""
    return int(getattr(mem, "temp_size_in_bytes", 0)
               + getattr(mem, "argument_size_in_bytes", 0)
               + getattr(mem, "output_size_in_bytes", 0)
               - getattr(mem, "alias_size_in_bytes", 0))


def is_out_of_memory(err: BaseException) -> bool:
    """The compiler's (or the runtime's) "this does not fit the device": XLA
    raises it as RESOURCE_EXHAUSTED, "Ran out of memory in memory space
    hbm"."""
    text = f"{type(err).__name__}: {err}".lower()
    return "resource_exhausted" in text or "out of memory" in text


def _host_peak_rss_gb() -> float:
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    # linux reports KB; darwin reports bytes
    return rss / (1024 ** 3 if sys.platform == "darwin" else 1024 ** 2)


def see_memory_usage(message: str, force: bool = False) -> Optional[Dict]:
    """Log device + host memory (reference ``see_memory_usage``).  Like the
    reference, silent unless ``force`` (scripts gate it on a debug flag).
    Returns the stats dict for programmatic use."""
    if not force:
        return None
    from ..accelerator import get_accelerator

    accel = get_accelerator()
    try:
        stats = accel.memory_stats() or {}
    except Exception:
        stats = {}
    host_rss_gb = _host_peak_rss_gb()
    g = 1024 ** 3
    if stats.get("bytes_in_use") is not None:
        in_use = stats.get("bytes_in_use", 0) / g
        peak = stats.get("peak_bytes_in_use", 0) / g
        limit = stats.get("bytes_limit", 0) / g
        logger.info(f"{message} | device MA {in_use:.2f} GB, "
                    f"peak {peak:.2f} GB, limit {limit:.2f} GB "
                    f"| host peak RSS {host_rss_gb:.2f} GB")
        device = {"in_use_gb": in_use, "peak_gb": peak, "limit_gb": limit}
    else:
        logger.info(f"{message} | device stats n/a on "
                    f"{accel.device_name()} | host peak RSS "
                    f"{host_rss_gb:.2f} GB")
        device = None
    return {"device": device, "host_peak_rss_gb": host_rss_gb}


def memory_status(msg: str, print_rank: int = -1,
                  reset_max: bool = False) -> Optional[Dict]:
    """Reference ``memory_status`` shape: rank-gated device memory print.
    ``reset_max`` is accepted but inert — XLA exposes no peak reset; the
    peak is since process start."""
    if print_rank >= 0:
        import jax

        if jax.process_index() != print_rank:
            return None
    return see_memory_usage(f"memory_status: {msg}", force=True)
