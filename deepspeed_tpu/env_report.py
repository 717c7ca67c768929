"""``ds_report`` equivalent (reference ``deepspeed/env_report.py``).

Prints the software stack, device inventory, and op/kernel availability so a
bug report carries the whole environment.  Run as
``python -m deepspeed_tpu.env_report`` (add ``--hide_operator_status`` /
``--hide_errors_and_warnings`` for terser output, flag parity with the
reference CLI).
"""
from __future__ import annotations

import argparse
import importlib
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[93m[NO]\033[0m"


def _ver(mod: str) -> str:
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "unknown")
    except Exception:
        return "not installed"


def op_status():
    """kernel/op availability: (name, importable, functional)."""
    rows = []

    def probe(name, fn):
        try:
            fn()
            rows.append((name, True, True))
        except ImportError:
            rows.append((name, False, False))
        except Exception:
            rows.append((name, True, False))

    probe("pallas.flash_attention",
          lambda: importlib.import_module(
              "deepspeed_tpu.ops.pallas.flash_attention"))
    probe("ring_attention",
          lambda: importlib.import_module("deepspeed_tpu.ops.ring_attention"))
    probe("quantizer (int8/int4 collectives)",
          lambda: importlib.import_module("deepspeed_tpu.ops.quantizer"))
    try:
        from deepspeed_tpu.ops.op_builder import ALL_OPS

        for name, builder in ALL_OPS.items():
            b = builder()
            rows.append((f"native.{name}", b.is_compatible(), b.is_built()))
    except ImportError:
        pass
    return rows


def devices_report():
    """Device inventory as JAX reports it."""
    import jax

    devs = jax.devices()
    lines = []
    lines.append(f"platform ............. {devs[0].platform}")
    lines.append(f"local devices ........ {jax.local_device_count()}")
    lines.append(f"global devices ....... {jax.device_count()}")
    lines.append(f"process index ........ {jax.process_index()}/{jax.process_count()}")
    for d in devs[:8]:
        kind = getattr(d, "device_kind", "?")
        lines.append(f"  [{d.id}] {kind}")
    if len(devs) > 8:
        lines.append(f"  ... and {len(devs) - 8} more")
    return lines


def main(args=None) -> int:
    ap = argparse.ArgumentParser(prog="ds_report")
    ap.add_argument("--hide_operator_status", action="store_true")
    ap.add_argument("--hide_errors_and_warnings", action="store_true")
    opts = ap.parse_args(args)

    import deepspeed_tpu

    print("-" * 66)
    print("DeepSpeed-TPU C++/Pallas op report")
    print("-" * 66)
    if not opts.hide_operator_status:
        print(f"{'op name':<40}{'compatible':<14}{'built/functional'}")
        print("-" * 66)
        for name, compat, built in op_status():
            print(f"{name:<40}"
                  f"{GREEN_OK if compat else RED_NO:<23}"
                  f"{GREEN_OK if built else RED_NO}")
    print("-" * 66)
    print("General environment:")
    print(f"deepspeed_tpu ........ {deepspeed_tpu.__version__} "
          f"({deepspeed_tpu.__path__[0]})")
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        print(f"{mod:<21}{_ver(mod)}")
    print(f"python ............... {sys.version.split()[0]}")
    print("-" * 66)
    print("Device inventory:")
    for line in devices_report():
        print(line)
    print("-" * 66)
    return 0


if __name__ == "__main__":
    sys.exit(main())
