"""Collective micro-benchmarks (reference ``benchmarks/communication/run_all.py``).

Times each collective as its own jitted shard_map program over the active
mesh's devices and reports latency, algorithm bandwidth, and bus bandwidth.
Bus-bandwidth factors follow the standard ring-algorithm accounting (the same
convention the reference's busbw column uses, communication/utils.py):

  all_reduce      busbw = algbw * 2(n-1)/n
  all_gather      busbw = algbw *  (n-1)/n
  reduce_scatter  busbw = algbw *  (n-1)/n
  all_to_all      busbw = algbw *  (n-1)/n
  broadcast       busbw = algbw *  (n-1)/n   (modeled by its ring equivalent:
                                              every rank must END with the full
                                              payload, which moves the same
                                              (n-1)/n * S per link as all_gather)

where algbw = payload_bytes / time.  Payload is the GLOBAL tensor size, so
numbers are comparable with the reference's tables.

Run: ``python -m deepspeed_tpu.comm.benchmark [--op all] [--maxsize 27]``
(sizes are powers of two in bytes, 2^15..2^maxsize). Works on the real chip
pool or the virtual CPU mesh alike.
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, Dict

BUSBW_FACTOR: Dict[str, Callable[[int], float]] = {
    "all_reduce": lambda n: 2 * (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "broadcast": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
}


def _mesh_and_axis():
    import jax
    from jax.sharding import Mesh
    import numpy as np

    devs = jax.devices()
    return Mesh(np.array(devs), ("x",)), "x", len(devs)


def _programs(axis):
    import jax
    from jax import lax

    return {
        "all_reduce": lambda x: lax.psum(x, axis),
        "all_gather": lambda x: lax.all_gather(x, axis, tiled=True),
        "reduce_scatter": lambda x: lax.psum_scatter(x, axis, tiled=True),
        "all_to_all": lambda x: lax.all_to_all(
            x.reshape(jax.device_count(), -1), axis, 0, 0, tiled=True),
        # ring-equivalent broadcast: every rank ends holding the full payload
        "broadcast": lambda x: lax.all_gather(x, axis, tiled=True),
        "ppermute": lambda x: lax.ppermute(
            x, axis, [(i, (i + 1) % jax.device_count())
                      for i in range(jax.device_count())]),
    }


def run_op(op: str, global_bytes: int, trials: int = 20, warmups: int = 3,
           dtype=None):
    """Time one collective at one size; returns a result dict."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh, axis, n = _mesh_and_axis()
    dtype = dtype or jnp.float32
    elem = jnp.dtype(dtype).itemsize
    # round the per-device count up to a multiple of n so all_to_all's
    # n-way re-split is always exact
    per_dev = max(global_bytes // (n * elem), 1)
    per_dev = -(-per_dev // n) * n
    body = _programs(axis)[op]
    specs = dict(mesh=mesh, in_specs=P("x"),
                 out_specs=P("x") if op != "broadcast" else P())
    # tiled all_gather output IS replicated, but shard_map's varying-axes
    # check can't see through it
    fn = jax.jit(shard_map(body, check_vma=op != "broadcast", **specs))
    x = jax.device_put(
        jnp.ones((n * per_dev,), dtype),
        NamedSharding(mesh, P("x")))
    out = x
    for _ in range(warmups):
        out = fn(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(trials):
        out = fn(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / trials
    payload = n * per_dev * elem
    algbw = payload / dt
    return {
        "op": op, "size_bytes": payload, "n_devices": n,
        "latency_us": dt * 1e6, "algbw_gbps": algbw / 1e9,
        "busbw_gbps": algbw * BUSBW_FACTOR[op](n) / 1e9,
    }


def run_sweep(ops=None, min_pow: int = 15, max_pow: int = 27, trials: int = 20,
              print_table: bool = True):
    ops = ops or list(BUSBW_FACTOR)
    rows = []
    for op in ops:
        for p in range(min_pow, max_pow + 1, 3):
            rows.append(run_op(op, 1 << p, trials=trials))
    if print_table:
        hdr = (f"{'op':<16}{'size':>12}{'lat(us)':>12}{'algbw GB/s':>12}"
               f"{'busbw GB/s':>12}")
        print(hdr)
        print("-" * len(hdr))
        for r in rows:
            print(f"{r['op']:<16}{r['size_bytes']:>12}{r['latency_us']:>12.1f}"
                  f"{r['algbw_gbps']:>12.2f}{r['busbw_gbps']:>12.2f}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(prog="deepspeed_tpu.comm.benchmark")
    ap.add_argument("--op", default="all",
                    choices=["all"] + list(BUSBW_FACTOR))
    ap.add_argument("--minsize", type=int, default=15, help="log2 min bytes")
    ap.add_argument("--maxsize", type=int, default=27, help="log2 max bytes")
    ap.add_argument("--trials", type=int, default=20)
    args = ap.parse_args(argv)
    ops = list(BUSBW_FACTOR) if args.op == "all" else [args.op]
    run_sweep(ops, args.minsize, args.maxsize, args.trials)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
