"""What the benchmark takes from the system under test, in one place: the
device gate, the compile cache, the model a configuration file names,
seeded weights, the compile meter.

``random_bf16_params`` and ``Meter`` are ``chip_smoke.py``'s, copied so the
yardstick does not move when the smoke does.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def jax_seed(seed: int) -> int:
    """``--seed`` may exceed 32 signed bits; JAX keys take 31."""
    return int(seed) % (2 ** 31 - 1)


def device_record(chips: int, rehearse: bool) -> Dict[str, Any]:
    """The device as JAX reports it.  Raises unless it is a TPU with at
    least ``chips`` chips (or the rehearsal's CPU)."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if rehearse:
        if dev.platform != "cpu":
            raise RuntimeError("--rehearse runs on the CPU; JAX holds "
                               f"{dev.platform}")
    elif dev.platform != "tpu":
        raise RuntimeError(f"no TPU: jax.devices()[0] is {dev!r}; the "
                           "benchmark does not fall back")
    if len(devs) < chips:
        raise RuntimeError(f"cell needs {chips} chip(s), JAX sees {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips the cell used."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def transformer_config(config: Dict[str, Any], rehearse: bool):
    """The ``TransformerConfig`` a configuration file describes: a named
    base plus overrides, or the fields outright."""
    from deepspeed_tpu.models import TransformerConfig, get_config

    spec = config["rehearse_transformer_config" if rehearse
                  else "transformer_config"]
    if "base" in spec:
        return get_config(spec["base"], **spec.get("overrides", {}))
    return TransformerConfig(**spec["fields"])


def random_bf16_params(cfg, seed: int):
    """Random weights from a seed, made on the device in one jitted call and
    born bf16: the fp32 tree never sits on the device."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import init_params

    def init(rng):
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            init_params(cfg, rng))

    return jax.jit(init)(jax.random.PRNGKey(jax_seed(seed)))


class Meter:
    """Backend compiles and persistent-cache hits and misses, process-wide;
    callers take deltas around the section they care about."""

    def __init__(self):
        from deepspeed_tpu.utils.compile_counter import (
            compile_counter, persistent_cache_counter)

        self.compiles = compile_counter()
        self._cache = persistent_cache_counter()

    def cache_misses(self) -> int:
        return self._cache()[1]
