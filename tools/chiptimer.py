"""Kernel timing that cancels dispatch overhead.

A kernel that runs for microseconds cannot be timed per call: the host's
dispatch and the completion wait cost as much as the kernel.  The recipe,
used by every tool in this directory:

- chain K applications of the op inside ONE jitted ``lax.scan`` (one
  dispatch, real device time, data dependencies prevent elision),
- return a scalar reduction of the final carry and ``float()`` it, which
  waits for the device and consumes the result inside the timed region,
- run at two K values and report ``(t(K2) - t(K1)) / (K2 - K1)`` — the
  constant dispatch+fetch overhead cancels exactly.

(``jax.block_until_ready`` is an equally valid join on the attached TPU:
``tests/unit/test_tpu_hardware.py::test_block_until_ready_waits`` holds it
to the fetch-terminated time.)
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp


def _chained(step: Callable, K: int):
    """jit(args -> scalar) running ``step`` K times with data dependency.

    ``step(args) -> args`` must be shape-preserving (chain outputs back in).
    """

    @jax.jit
    def run(args):
        def body(c, _):
            return step(c), None

        final, _ = jax.lax.scan(body, args, None, length=K)
        return sum(jnp.sum(x.astype(jnp.float32))
                   for x in jax.tree_util.tree_leaves(final))

    return run


def device_time(step: Callable, args, k_small: int = 8, k_big: int = 64,
                repeats: int = 5) -> float:
    """Seconds per application of ``step`` on the device, overhead-free.

    MEDIAN of the difference quotients: host jitter in the SMALL run
    inflates t1 and a min would then report impossibly-fast kernels; the
    median survives isolated spikes.
    If the big chain is too short to rise above jitter, K doubles until
    the big run takes >=30ms more than the small one.
    """
    while True:
        runs = {k: _chained(step, k) for k in (k_small, k_big)}
        for k in (k_small, k_big):
            float(runs[k](args))  # compile + first-fetch outside the timing

        def once(k):
            t0 = time.perf_counter()
            float(runs[k](args))
            return time.perf_counter() - t0

        samples = []
        for _ in range(repeats):
            t1, t2 = once(k_small), once(k_big)
            samples.append((t2 - t1) / (k_big - k_small))
        samples.sort()
        med = samples[len(samples) // 2]
        if med * (k_big - k_small) >= 0.03:
            return med
        if k_big >= 4096:
            if med <= 0:
                # returning 0 here would flow into divisions downstream;
                # fail loudly instead
                raise RuntimeError(
                    "device_time: host jitter exceeded the signal even at "
                    f"K={k_big}; cannot time this op honestly")
            return med
        k_small, k_big = k_small * 4, k_big * 4
