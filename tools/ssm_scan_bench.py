#!/usr/bin/env python3
"""A prompt's selective scan by shape, on the chip: the chunked form in
``jax.numpy`` (``models.mixers.ssm._ssm_scan``) against
``ops/pallas/ssm_scan.py`` on the same operands.

    chiprun --chips 1 -- python3 tools/ssm_scan_bench.py
    JAX_PLATFORMS=cpu python3 tools/ssm_scan_bench.py --interpret --cases tiny

One JSON line a (case, implementation): milliseconds a layer's call (a call
is ``--reps`` scans chained in one program, each from the state the one
before it left, every ``y`` an output: a dispatch costs this host ~0.4 ms,
more than a scan; the median of five batches of 20 calls dispatched back to
back and waited for once), the least time the chip could take beside it (the
operands once:
``x``, ``B``, ``C``, ``dt`` and the state in, ``y`` and the state out, at the
chip's bandwidth; the chunked form's four products at its bf16 peak), and the
largest difference of ``y`` and of the state from ``_ssm_scan``'s.  The cases
are a state-space layer of the benchmark's two cells as a prefill program
hands it over: one row, the bucket's tokens (Granite's a piece of
``SSM_BLOCK_TOKENS``), bfloat16 operands, a float32 state behind a first
piece.  ``kernel/<n>`` holds ``n`` heads a grid step; ``rule`` is what
:func:`ssm_scan_path` and the tile plan choose for the shape.  The table is
what ``ssm_scan.HEAD_BLOCK`` is set from.  A tool, run by no cell (PERF.md
section 5, PR 59)."""
import argparse
import functools
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_BYTES, PEAK_FLOPS = 819e9, 197e12          # one v5e (PERF.md section 3)

# name: (heads, head width, state, groups, chunk, tokens of the one row)
GRANITE, FALCON = (128, 64, 128, 1, 256), (32, 128, 256, 2, 128)
CASES = {
    # granite-4.0-h-small-ep2-d10.ragdoc-backlog: a piece of every bucket
    # (2,048 to 16,384 run in pieces of 2,048), and a short prompt
    "granite_piece": (*GRANITE, 2048),
    "granite_512": (*GRANITE, 512),
    # falcon-h1-34b-d5.chatburst-backlog: its three busiest buckets
    "falcon_256": (*FALCON, 256),
    "falcon_512": (*FALCON, 512),
    "falcon_1024": (*FALCON, 1024),
    "tiny": (4, 64, 128, 1, 128, 200),
    "tiny_groups": (16, 128, 128, 2, 128, 256),
}


def operands(case, dtype, seed=0):
    H, P, N, G, _, T = CASES[case]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x, Bm, Cm = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                 for kk, shape in zip(k, ((1, T, H, P), (1, T, G, N),
                                          (1, T, G, N))))
    # dt and A in the ranges the mixer's initial draw gives them
    dt = jax.nn.softplus(jax.random.normal(k[3], (1, T, H)) - 3.0)
    A = -jax.random.uniform(k[4], (H,), minval=1.0, maxval=16.0)
    state = jax.random.normal(k[5], (1, H, P, N), jnp.float32)
    return x, Bm, Cm, dt, A, state


def least_ms(case, itemsize):
    """``(bytes', operations')`` least milliseconds of one call."""
    H, P, N, G, Q, T = CASES[case]
    moved = (T * H * P * (itemsize + 4) + 2 * T * G * N * itemsize
             + T * H * 4 + 2 * H * P * N * 4)
    ops = T * (H * (2 * Q * P + 4 * P * N) + G * 2 * Q * N)
    return moved / PEAK_BYTES * 1e3, ops / PEAK_FLOPS * 1e3


def ms_a_call(fn, args, calls=20, batches=5):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(calls):
            y = fn(*args)
        jax.block_until_ready(y)
        out.append((time.perf_counter() - t0) / calls * 1e3)
    return statistics.median(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default=",".join(
        c for c in CASES if not c.startswith("tiny")))
    ap.add_argument("--only", default="xla,kernel/8,kernel/16")
    ap.add_argument("--reps", type=int, default=8,
                    help="scans chained in one program")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--interpret", action="store_true",
                    help="the CPU rehearsal: the kernel in interpret mode")
    ap.add_argument("--out", default="chiprun_out/ssm_scan_bench.jsonl")
    a = ap.parse_args()
    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models.mixers import common as MX
    from deepspeed_tpu.models.mixers import ssm as SSM
    from deepspeed_tpu.ops.pallas import ssm_scan as K

    dev = jax.devices()[0]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    sink = open(a.out, "a")
    calls = (1, 1) if a.interpret else (20, 5)
    dtype = jnp.dtype(a.dtype)

    def say(**rec):
        line = json.dumps(dict(rec, device=dev.device_kind))
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for case in a.cases.split(","):
        H, P, N, G, Q, T = CASES[case]
        cfg = get_config("falcon-h1-34b", num_layers=1, ssm_heads=H,
                         ssm_head_dim=P, ssm_state=N, ssm_groups=G,
                         ssm_chunk=Q, dtype=dtype)
        args = operands(case, dtype)
        by_bytes, by_ops = least_ms(case, dtype.itemsize)

        def scan(impl):
            if impl == "xla":
                return functools.partial(SSM._ssm_scan, cfg)
            return functools.partial(
                K.ssm_scan, chunk=Q, interpret=a.interpret,
                block=int(impl.split("/")[1]) if "/" in impl else None)

        def chained(once):
            def f(x, Bm, Cm, dt, A, state):
                ys = []
                for _ in range(a.reps):
                    y, state = once(x, Bm, Cm, dt, A, state)
                    ys.append(y)
                return ys, state
            return jax.jit(f)

        want = None
        for impl in a.only.split(","):
            try:
                ms = ms_a_call(chained(scan(impl)), args, *calls) / a.reps
                y, s = (np.asarray(o, np.float32)
                        for o in jax.jit(scan(impl))(*args))
            except Exception as e:  # noqa: BLE001 - a shape the chip refuses
                say(case=case, impl=impl,
                    error=f"{type(e).__name__}: {str(e)[:300]}")
                continue
            if want is None:
                want = y, s
            say(case=case, impl=impl, tokens=T, heads=[H, P], state=N,
                groups=G, chunk=Q, ms=round(ms, 4),
                least_ms_bytes=round(by_bytes, 4),
                least_ms_ops=round(by_ops, 4),
                roof_share=round(max(by_bytes, by_ops) / ms, 4),
                y_max=float(np.abs(want[0]).max()),
                y_err=float(np.abs(y - want[0]).max()),
                state_err=float(np.abs(s - want[1]).max()))
        MX._pallas_interpret = lambda: a.interpret
        say(case=case, impl="rule", block=K.scan_block(H, G, P, N, Q),
            path=SSM.ssm_scan_path(cfg, T))
    sink.close()


if __name__ == "__main__":
    sys.exit(main())
