#!/usr/bin/env python3
"""A decode tick's paged read by shape, on the chip: the gather-then-attend
loop of ``models.transformer._attention_paged`` against
``ops/pallas/paged_read.py`` on the same pool, plan and queries.

    chiprun --chips 1 -- python3 tools/paged_read_bench.py
    JAX_PLATFORMS=cpu python3 tools/paged_read_bench.py --interpret --cases tiny

One JSON line a (case, implementation): milliseconds a layer's read (a call
is ``--reps`` reads chained in one program; the median of five batches of 20
calls dispatched back to back and waited for once), the share of the read's
roof (the live pages' K and V bytes once, at the chip's bandwidth) and the
largest difference from the gather's output.
The cases are the attention layers of the benchmark's serving cells as a tick
hands them over: slots, heads, the page and how the device stores the leaf,
and slots' lengths as the cell's traffic makes them.  ``kernel/<n>`` takes
``n`` pairs a grid step; ``rule`` is what :func:`kv_read_path` chooses for
the shape.  The table is what ``paged_read.MIN_BLOCK_BYTES`` is set from
(PERF.md section 5, PR 52).

The ``kanana`` cases are a latent leaf's (PR 57): one row ``[c ; k_pe]`` a
token and no head axis, stored page-rows-minor; ``gather`` is
``_attention_latent_paged``'s loop and ``kernel/<n>`` the same function
where the rule says ``"pages"`` (``paged_read.latent_read``), both with the
absorbed products around the read (32 rows against ``wkv_b``: under 1% of
either); the roof counts the live pages' latent bytes once."""
import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_BYTES = 819e9                              # one v5e (PERF.md section 2)
HEAD_MAJOR = (0, 1, 3, 2, 4)
LATENT = (0, 1, 3, 2)           # transformer.LATENT_PAGE_ROWS_MINOR

# name: (slots, Hq, Hkv, head width, page, pages a table row, the stored
# order of the unstacked leaf, (live slots, shortest, longest) rows held);
# a latent leaf's: its value columns (r) for Hkv, its row (r + rd) for the
# head width
CASES = {
    # kanana-2-30b-a3b-ep8-d24.longctx-backlog: the first fill (20 prompts
    # in) and the middle of the window (every slot live, 2k-7k rows behind it)
    "kanana_fill": (32, 32, 512, 576, 128, 64, LATENT, (20, 2048, 6144)),
    "kanana_window": (32, 32, 512, 576, 128, 64, LATENT, (32, 2300, 7000)),
    "tiny_latent": (3, 4, 128, 144, 128, 3, LATENT, (2, 100, 380)),
    # olmo-hybrid-7b-d16.thinkrollout-backlog: the first fill (prompts and a
    # little more) and the middle of the window
    "olmo_fill": (32, 30, 30, 128, 128, 16, HEAD_MAJOR, (32, 400, 800)),
    "olmo_window": (32, 30, 30, 128, 128, 16, HEAD_MAJOR, (32, 500, 1800)),
    # ouro-2.6b.mathrollout-backlog: pages, not slots, bound the batch
    "ouro": (16, 16, 16, 128, 128, 8, None, (9, 200, 700)),
    "olmoe": (16, 16, 16, 128, 128, 16, None, (16, 300, 1500)),
    "falcon": (96, 20, 4, 128, 128, 16, HEAD_MAJOR, (96, 200, 1200)),
    "granite": (32, 32, 8, 128, 128, 104, None, (32, 4000, 12000)),
    "tiny": (3, 4, 2, 128, 16, 4, HEAD_MAJOR, (2, 10, 60)),
    "tiny_rows": (3, 16, 16, 128, 8, 4, None, (3, 3, 30)),
}


def operands(case, seed=0):
    slots, hq, hkv, hd, page, maxp, order, (live, lo, hi) = CASES[case]
    rng = np.random.default_rng(seed)
    n = 1 + slots * maxp
    key = jax.random.PRNGKey(seed)
    if order == LATENT:
        # the leaf as the device stores it; K's place holds it, V's wkv_b
        # [r, Hq * (nope + vd)]; queries of nope + rd = 128 + (hd - r)
        k = jax.random.normal(jax.random.fold_in(key, 1), (n, hd, page),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 2),
                              (hkv, hq * 256), jnp.bfloat16) * hkv ** -0.5
        q = jax.random.normal(key, (slots, 1, hq, 128 + hd - hkv),
                              jnp.bfloat16)
    else:
        shape = ((n, hkv, page, hd) if order == HEAD_MAJOR
                 else (n, page, hkv, hd))
        k, v = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.bfloat16) for i in (1, 2))
        q = jax.random.normal(key, (slots, 1, hq, hd), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(n - 1)[:slots * maxp].reshape(
        slots, maxp) + 1, jnp.int32)
    start = np.zeros(slots, np.int32)
    start[:live] = rng.integers(lo, hi, live)
    mask = jnp.asarray((np.arange(slots) < live)[:, None])
    return q, k, v, table, jnp.asarray(start), mask


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
    ap.add_argument("--pairs", default="1,2,4,8")
    ap.add_argument("--latent-pairs", default="1,3,6,8",
                    help="pairs a grid step, the latent cases'")
    ap.add_argument("--reps", type=int, default=8,
                    help="reads chained in one program")
    ap.add_argument("--interpret", action="store_true",
                    help="the CPU rehearsal: the kernel in interpret mode")
    ap.add_argument("--out", default="chiprun_out/paged_read_bench.jsonl")
    a = ap.parse_args()
    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX
    from deepspeed_tpu.ops.pallas import paged_read as PR

    dev = jax.devices()[0]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    sink = open(a.out, "a")
    calls = (1, 1) if a.interpret else (20, 5)

    def say(**rec):
        line = json.dumps(dict(rec, device=dev.device_kind))
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for case in a.cases.split(","):
        slots, hq, hkv, hd, page, maxp, order, _ = CASES[case]
        latent = order == LATENT
        cfg = (get_config("kanana-2-30b-a3b", num_layers=1, num_heads=hq,
                          kv_lora_rank=hkv, rotary_dim=hd - hkv,
                          head_dim=128 + hd - hkv, v_head_dim=128,
                          dtype=jnp.bfloat16) if latent else
               get_config("olmoe-1b-7b", num_layers=1, num_heads=hq,
                          num_kv_heads=hkv, head_dim=hd, hidden_size=hq * hd,
                          dtype=jnp.bfloat16))
        q, k, v, table, start, mask = operands(case)
        read = jax.jit(lambda t, s, m: T._paged_read_plan(t, s, m, page))(
            table, start, mask)
        pairs_live = int(jnp.sum(read[1] < slots))
        # K's and V's block of a pair; a latent pair's one block is both
        block = (hd if latent else hkv * hd) * page * 2
        roof_ms = pairs_live * (1 if latent else 2) * block / PEAK_BYTES * 1e3

        def logical(a):          # as forward_paged hands a head-major leaf
            return jnp.transpose(a, (0, 2, 1, 3)) if order else a

        def chained(read_once):
            """``a.reps`` reads in one program, each one's queries moved by
            the read before it: a call's dispatch (~0.2 ms on this host) is
            paid once, and no read can be dropped as a copy of another."""
            def f(q, k, v, read):
                out = read_once(q, k, v, read)
                for _ in range(a.reps - 1):
                    # (a latent read's output is not as wide as its queries)
                    out = read_once(q + (out if out.shape == q.shape
                                         else out[..., :1]) * 1e-3,
                                    k, v, read)
                return out
            return jax.jit(f)

        def attend(interpret):
            def f(q, k, v, read):
                MX._pallas_interpret = lambda: interpret
                return T._attention_paged(
                    cfg, q, {"k": logical(k), "v": logical(v)}, read, order)
            return f

        def kernel(n):
            def f(q, k, v, read):
                _, slot, pages, limit = read
                slot = slot.reshape(-1)
                acc, l = PR.paged_read(
                    q[:, 0], k, v, jnp.sum(slot < slots, dtype=jnp.int32),
                    slot, pages.reshape(-1), limit.reshape(-1),
                    axes="ktd" if order else "tkd", scale=hd ** -0.5,
                    pairs=n, interpret=a.interpret)
                return (acc / jnp.where(l > 0, l, 1.0)[..., None]
                        ).astype(q.dtype)[:, None]
            return f

        def latent_attend(interpret, n=None):
            """``_attention_latent_paged`` where no kernel may run (the
            gather), or where one may, ``n`` pairs a step."""
            def f(q, c, wkv_b, read):
                MX._pallas_interpret = lambda: interpret
                if n is not None:
                    # whatever the block's bytes: this table sets the bound
                    PR.pairs_a_step, PR.MIN_BLOCK_BYTES = (
                        lambda block, step: n), 0
                    PR.latent_read.clear_cache()    # a trace a shape
                return T._attention_latent_paged(
                    cfg, q, wkv_b, jnp.transpose(c, (0, 2, 1)), read, order)
            return f

        want = None

        def report(impl, fn):
            nonlocal want
            try:
                ms = ms_a_call(chained(fn), (q, k, v, read), *calls) / a.reps
                got = np.asarray(jax.jit(fn)(q, k, v, read), np.float32)
            except Exception as e:  # noqa: BLE001 - a shape the chip refuses
                say(case=case, impl=impl,
                    error=f"{type(e).__name__}: {str(e)[:300]}")
                return
            if want is None:
                want = got
            say(case=case, impl=impl, slots=slots, heads=[hq, hkv],
                page=page, block_bytes=block, live_pairs=pairs_live,
                steps=int(read[0]), ms=round(ms, 4), roof_ms=round(roof_ms, 4),
                roof_share=round(roof_ms / ms, 4),
                max_err=float(np.abs(got - want).max()))

        if latent:
            report("gather", latent_attend(None))
            if PR.latent_block(k.shape, hkv, k.dtype) is None:
                say(case=case, impl="kernel",
                    error="no tile plan for the leaf")
                continue
            rule = PR.pairs_a_step, PR.MIN_BLOCK_BYTES
            for n in a.latent_pairs.split(","):
                report(f"kernel/{n}", latent_attend(a.interpret, int(n)))
            PR.pairs_a_step, PR.MIN_BLOCK_BYTES = rule
            say(case=case, impl="rule", block_bytes=block,
                pairs_a_step=PR.pairs_a_step(block, PR.LATENT_STEP_BYTES),
                path=T.kv_read_path(
                    {"latent": jax.ShapeDtypeStruct((k.shape[0], page, hd),
                                                    k.dtype)},
                    order, jax.ShapeDtypeStruct((slots, hq), q.dtype),
                    values=hkv))
            continue
        report("gather", attend(None))
        if PR.page_block(k.shape, v.shape, k.dtype,
                         "ktd" if order else "tkd") is None:
            say(case=case, impl="kernel", error="no tile plan for the leaf")
            continue
        for n in a.pairs.split(","):
            report(f"kernel/{n}", kernel(int(n)))
        MX._pallas_interpret = lambda: a.interpret
        say(case=case, impl="rule", block_bytes=block, path=T.kv_read_path(
            {n: jax.eval_shape(logical, a) for n, a in (("k", k), ("v", v))},
            order, jax.ShapeDtypeStruct((slots, hq), q.dtype)))
    sink.close()


if __name__ == "__main__":
    sys.exit(main())
