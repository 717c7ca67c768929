#!/usr/bin/env python3
"""The sorted rows' way in and way back of a dropless expert layer, by
shape, on the chip: what ``moe_ffn_nodrop`` did before PR 50 (every sorted
row gathered in; a select over every row, a gather back and a float32 sum)
against ``moe/live_rows.py`` (the live rows alone, in tiles under a loop
whose trip count is read on the device) on the same routing.

    chiprun --chips 1 -- python3 tools/moe_rows_bench.py [--only in] [--cases granite_full]

One JSON line a (case, way, form): milliseconds a call (the median of five
batches of 20 calls dispatched back to back and waited for once), the rows
that are live, the rows the form moves, and the least bytes' share of the
chip's bandwidth (the live rows read once and written once on the way in;
read once, with the tokens' results written, on the way back).  The cases
are a call of the expert layer in the benchmark's cells: a chunk of a
prompt, its tokens' pairs spread evenly over the router's experts, the
pairs of an expert held elsewhere and of a token past the prompt's end in
no group.  The forms:

  in    today       ``x[tok]`` over every sorted row
        loop<t>     ``live_rows.rows_in`` at a tile of ``t`` rows (the
                    shipped tile is ``live_rows.ROWS_IN_TILE``)
        loop<t>_zeros   the same into a buffer of zeros (``jnp.zeros``)
                    in place of one nobody wrote
  back  today       ``where(live, out, 0)[inv]`` and the float32 sum
        loop<t>     ``live_rows.rows_back`` at a tile of ``t`` tokens (the
                    shipped tile is ``live_rows.TOKENS_BACK_TILE``)

With --experiments also the forms that were tried and not taken:

  in    loop<t>_u32     the rows gathered as uint32 (two bfloat16 columns a
                    word, packed first and unpacked in the tile)
  back  loop<t>_token_major   the tile's rows fetched [t, k, D] instead of
                    [k, t, D]
        loop<t>_u32, loop<t>_u32_prepacked   the rows gathered as uint32,
                    with and without the pass that packs ``out``

PERF.md section 5 (PR 50) holds the table this printed on the v5e."""
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

from deepspeed_tpu.moe import live_rows  # noqa: E402

PEAK_BYTES = 819e9                      # one v5e (PERF.md section 2)

# name: (tokens, top_k, d_model, experts, held, real tokens)
CASES = {
    "granite_full": (2048, 10, 4096, 72, 36, 2048),
    "granite_quarter": (2048, 10, 4096, 72, 36, 512),
    "granite_padding": (2048, 10, 4096, 72, 36, 0),
    "kanana": (2048, 6, 2048, 128, 16, 2048),
    "mimo": (2048, 8, 4096, 256, 16, 2048),
    "olmoe": (512, 8, 2048, 64, 64, 512),
    "olmoe_ragged_tail": (512, 8, 2048, 64, 64, 300),
    # a rehearsal's size (--interpret on the CPU), not in the default list
    "tiny": (96, 4, 128, 8, 4, 70),
}
INTERPRET = False                       # --interpret: a rehearsal on the CPU
IN_TILES = (256, 512, 1024, 2048)
BACK_TILES = (128, 256, 512)


def routing(case, seed=0):
    """The sorted order of a call, as ``moe_ffn_nodrop`` makes it."""
    T, k, D, E, held, real = CASES[case]
    rng = np.random.default_rng(seed)
    idx = np.argsort(rng.random((T, E)), axis=1)[:, :k]
    flat = np.where(idx < held, idx, held)
    flat = np.where((np.arange(T) < real)[:, None], flat, held).reshape(-1)
    order = np.argsort(flat, kind="stable")
    inv = np.argsort(order).reshape(T, k)
    n_live = int((flat < held).sum())
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (T, D), jnp.bfloat16)
    out = jax.random.normal(jax.random.fold_in(key, 1), (T * k, D),
                            jnp.bfloat16)
    gates = jax.random.uniform(jax.random.fold_in(key, 2), (T, k),
                               jnp.float32)
    return dict(x=x, out=out, gates=gates,
                tok=jnp.asarray(np.minimum(order // k, T - 1), jnp.int32),
                inv=jnp.asarray(inv, jnp.int32),
                live=jnp.asarray(np.sort(flat) < held),
                n_live=jnp.int32(n_live), n_tokens=jnp.int32(real)), n_live


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


def today_in(x, tok):
    return x[tok]


def today_back(out, live, inv, gates):
    T, k = inv.shape
    got = jnp.where(live[:, None], out, 0)[inv.reshape(-1)]
    return jnp.sum(got.reshape(T, k, -1).astype(jnp.float32)
                   * gates[:, :, None], axis=1).astype(out.dtype)


def shipped(name, tile, **patch):
    """A fresh jitted ``live_rows`` form traced with the module's tile (and
    anything else in ``patch``) set for the trace."""
    fn = {"in": lambda x, tok, n: live_rows._fill(x, tok, n, INTERPRET),
          "back": live_rows._sum}[name]
    knob = {"in": "ROWS_IN_TILE", "back": "TOKENS_BACK_TILE"}[name]

    def traced(*args):
        old = {n: getattr(live_rows, n) for n in (knob, *patch)}
        try:
            setattr(live_rows, knob, tile)
            for n, v in patch.items():
                setattr(live_rows, n, v)
            return fn(*args)
        finally:
            for n, v in old.items():
                setattr(live_rows, n, v)

    return jax.jit(traced)


def token_major_sum_tile(out, inv, gates, n_live):
    """The way back's tile as first written: the rows fetched ``[t, k, D]``
    (a re-layout of ``k`` rows into tiles of 8 or 16 behind the gather)."""
    held = inv < n_live
    got = out[jnp.where(held, inv, 0).reshape(-1)].reshape(*inv.shape, -1)
    return jnp.sum(jnp.where(held[:, :, None], got.astype(jnp.float32), 0)
                   * gates[:, :, None], axis=1)


def pack(a):
    """``[n, D]`` bfloat16 as ``[n, D/2]`` uint32: column ``c`` in the low
    half of a word, column ``c + D/2`` in the high half (32-bit rows are
    whole 512-byte pieces of the device's tiles; a bfloat16 row shares
    every word of its tile with the row beside it)."""
    half = a.shape[1] // 2
    bits = lambda v: jax.lax.bitcast_convert_type(  # noqa: E731
        v, jnp.uint16).astype(jnp.uint32)
    return bits(a[:, :half]) | (bits(a[:, half:]) << 16)


def unpack(u):
    """The two float32 halves of :func:`pack`'s words."""
    f = lambda v: jax.lax.bitcast_convert_type(v, jnp.float32)  # noqa: E731
    return f(u << 16), f(u & jnp.uint32(0xFFFF0000))


def in_u32(tile):
    def form(x, tok, n_live):
        rows = tok.shape[0]
        t = min(tile, rows)
        packed = pack(x)

        def step(i, xs):
            at = jnp.minimum(i * t, rows - t)
            lo, hi = unpack(packed[jax.lax.dynamic_slice(tok, (at,), (t,))])
            return jax.lax.dynamic_update_slice(
                xs, jnp.concatenate([lo, hi], 1).astype(x.dtype), (at, 0))

        return jax.lax.fori_loop(
            0, (n_live + t - 1) // t, step,
            live_rows._unwritten((rows, x.shape[1]), x.dtype, INTERPRET))
    return jax.jit(form)


def back_u32(tile, prepacked):
    def form(out, inv, gates, n_live, n_tokens):
        T, k = inv.shape
        t = min(tile, T)
        packed = out if prepacked else pack(out)

        def step(i, y):
            at = jnp.minimum(i * t, T - t)
            iv = jax.lax.dynamic_slice(inv, (at, 0), (t, k))
            g = jax.lax.dynamic_slice(gates, (at, 0), (t, k))
            held = iv < n_live
            got = packed[jnp.where(held, iv, 0).reshape(-1)].reshape(t, k, -1)
            part = jnp.concatenate([
                jnp.sum(jnp.where(held[:, :, None], h, 0) * g[:, :, None], 1)
                for h in unpack(got)], 1)
            return jax.lax.dynamic_update_slice(
                y, part.astype(y.dtype), (at, 0))

        return jax.lax.fori_loop(
            0, (n_tokens + t - 1) // t, step,
            jnp.zeros((T, 2 * packed.shape[1]), jnp.bfloat16))
    return jax.jit(form)


def main():
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--only", default="in,back",
                    help="the ways to time: in, back (default both)")
    ap.add_argument("--cases",
                    default=",".join(c for c in CASES if c != "tiny"),
                    help="comma-separated names of CASES (default all)")
    ap.add_argument("--out", default="chiprun_out/moe_rows_bench.jsonl",
                    help="the file the lines are appended to")
    ap.add_argument("--experiments", action="store_true",
                    help="also the forms that were tried and not taken")
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse on the CPU (counts only, no device time)")
    a = ap.parse_args()
    global INTERPRET
    INTERPRET = a.interpret
    ways = a.only.split(",")
    dev = jax.devices()[0]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    sink = open(a.out, "a")

    def say(**rec):
        line = json.dumps(dict(rec, device=dev.device_kind))
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for case in a.cases.split(","):
        T, k, D, E, held, real = CASES[case]
        r, n_live = routing(case)
        ref = {}

        def report(way, form, fn, args, moved, least, cut):
            try:
                ms = ms_a_call(fn, args)
                got = np.asarray(cut(fn(*args)), np.float32)
            except Exception as e:  # noqa: BLE001 - a form the chip refuses
                say(case=case, way=way, form=form,
                    error=f"{type(e).__name__}: {str(e)[:300]}")
                return
            ref.setdefault(way, got)
            say(case=case, way=way, form=form, tokens=T, top_k=k, d_model=D,
                experts=E, held=held, real_tokens=real, rows=T * k,
                live_rows=n_live, moved_rows=int(moved), ms=round(ms, 4),
                bandwidth_share=round(least / PEAK_BYTES / (ms * 1e-3), 4),
                finite=bool(np.isfinite(got).all()),
                max_err=float(np.abs(got - ref[way]).max()) if got.size
                else 0.0)

        if "in" in ways:
            least = 2 * 2 * n_live * D
            cut = lambda xs: xs[:n_live]  # noqa: E731
            report("in", "today", jax.jit(today_in), (r["x"], r["tok"]),
                   T * k, least, cut)
            for t in IN_TILES:
                args = (r["x"], r["tok"], r["n_live"])
                moved = -(-n_live // min(t, T * k)) * min(t, T * k)
                report("in", f"loop{t}", shipped("in", t), args, moved,
                       least, cut)
                report("in", f"loop{t}_zeros", shipped(
                    "in", t, _unwritten=lambda s, d, i: jnp.zeros(s, d)),
                    args, moved, least, cut)
                if a.experiments:
                    report("in", f"loop{t}_u32", in_u32(t), args, moved,
                           least, cut)
        if "back" in ways:
            least = 2 * (n_live + real) * D
            cut = lambda y: y  # noqa: E731
            report("back", "today", jax.jit(today_back),
                   (r["out"], r["live"], r["inv"], r["gates"]), 2 * T * k,
                   least, cut)
            for t in BACK_TILES:
                args = (r["out"], r["inv"], r["gates"], r["n_live"],
                        r["n_tokens"])
                moved = -(-real // min(t, T)) * min(t, T) * k
                report("back", f"loop{t}", shipped("back", t), args, moved,
                       least, cut)
                if a.experiments:
                    report("back", f"loop{t}_token_major", shipped(
                        "back", t, _sum_tile=token_major_sum_tile), args,
                        moved, least, cut)
                if a.experiments:
                    report("back", f"loop{t}_u32", back_u32(t, False), args,
                           moved, least, cut)
                    report("back", f"loop{t}_u32_prepacked",
                           back_u32(t, True), (pack(r["out"]),) + args[1:],
                           moved, least, cut)
    sink.close()


if __name__ == "__main__":
    sys.exit(main())
