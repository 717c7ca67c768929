#!/usr/bin/env python3
"""Does the system still start on the chip?  One process, the user's entry
points, full widths, random weights from a seed.

    python chip_smoke.py                 # on a TPU; anything else exits non-zero
    python chip_smoke.py --rehearse-cpu  # tiny sizes, interpret-mode kernels

Legs, in order; any failure raises and the process exits non-zero with no
result line:

  gate      jax.devices()[0].platform must be "tpu"
  train     deepspeed_tpu.initialize + train_batch: llama-740m, S=4096, bf16,
            ZeRO-1, one device
  serve     init_inference + ServingEngine.run: opt-1.3b, two request streams,
            one device
  numerics  the compiled flash kernel against the masked XLA path; paged
            prefill+decode logits against the plain forward
  zero3_dp4, serve_tp4
            when four devices are present: ZeRO-3 over dp=4 training opt-1.3b
            (too big for one chip, so running at all proves the partitioning),
            and a tensor-sharded serving engine

Stdout ends with two lines of JSON (the library's log lines come before).
The first is the summary: counts, losses, bytes
and set-up (compile) seconds per leg — no rate, utilisation or TFLOP/s: this
script measures nothing, and ``"claim"`` is always null.  The last is the
verdict and holds nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The rehearsal drives the same code at toy sizes on the CPU so chip time is not
spent on typos.  It prints its summary, which says ``"platform": "cpu"``, and
no verdict: it proves nothing about the chip.
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import os
import sys
import time

# One table per size.  FULL is what the chip runs; REHEARSAL only has to reach
# every line of this script on the CPU in about a minute.
FULL = dict(
    train_model="llama-740m", train_overrides={"max_seq_len": 4096},
    train_attn="auto",          # S=4096 resolves to the flash kernel
    train_seq=4096, train_micro_batch=2, train_steps=6,
    serve_model="opt-1.3b", serve_overrides={},
    b_slots=8, page_size=128, max_model_len=2048,
    # prompt lengths by prefill bucket (next power of two): 128 / 512 / 2048.
    # The second stream lands in the same buckets, so it may compile nothing.
    stream_a=[56, 56, 100, 128, 300, 400, 512, 450, 1100, 1500, 1900, 1300],
    stream_b=[70, 120, 90, 350, 500, 280, 1200, 1800, 1025, 110, 420, 1600],
    new_tokens=(32, 64),
    parity_prompt=200, parity_decode=4,
    zero3_model="opt-1.3b", zero3_overrides={}, zero3_seq=2048, zero3_steps=6,
    zero3_persistence_threshold=None,       # the engine's default
)
REHEARSAL = dict(
    train_model="tiny", train_overrides={"max_seq_len": 256},
    train_attn="pallas",        # S=256 would resolve to XLA; name the kernel
    train_seq=256, train_micro_batch=2, train_steps=6,
    serve_model="tiny-gpt2", serve_overrides={},
    b_slots=4, page_size=16, max_model_len=128,
    stream_a=[10, 10, 14, 16, 20, 28, 32, 25, 40, 50, 60, 45],
    stream_b=[12, 16, 9, 30, 22, 18, 48, 64, 33, 15, 27, 55],
    new_tokens=(8, 16),
    parity_prompt=40, parity_decode=4,
    zero3_model="tiny-gpt2", zero3_overrides={}, zero3_seq=128, zero3_steps=6,
    zero3_persistence_threshold=0,          # every toy leaf is "small"
)

# ---- tolerances, each with its reason -------------------------------------
# Flash kernel vs the masked XLA path run in float32 at "highest" matmul
# precision on the same bf16-valued inputs.  The kernel multiplies bf16
# operands into f32 accumulators and rounds the probabilities and the output
# to bf16 (8 significant bits, half-ulp 2^-9 relative); a handful of such
# roundings per element stays under 2^-6 of the largest reference value.
# A wrong mask, a dropped K block or a bad rescale shows up at order 1.
FLASH_REL_TOL = 2.0 ** -6
# Paged prefill/decode vs the plain forward, both in bf16, through all the
# layers: same weights and tokens, different attention formulation and
# reduction order.  Logits, not tokens — with random weights the argmax flips
# on rounding.  Small random weights make logits follow the current token far
# more than its context, so a fixed fraction of the logit scale would let a
# misplaced page through.  The yardstick is measured instead: how far the
# plain forward's own logits move when the first page of the prompt is
# replaced by other tokens.  The paged path must sit within a quarter of
# that — bf16 reordering noise is far below it, a lost page is at it.
PAGED_TOL_OF_ONE_PAGE = 0.25


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class Meter:
    """Compile requests, persistent-cache hits/misses, compile seconds and
    device memory, as deltas around a leg."""

    def __init__(self, device):
        from deepspeed_tpu.utils.compile_counter import (
            compile_counter, compile_seconds, persistent_cache_counter)

        self.device = device
        self.compiles = compile_counter()
        self.seconds = compile_seconds()
        self.cache = persistent_cache_counter()

    def snapshot(self):
        hits, misses = self.cache()
        return {"compiles": self.compiles(), "compile_s": self.seconds(),
                "cache_hits": hits, "cache_misses": misses}

    def since(self, snap):
        now = self.snapshot()
        out = {k: now[k] - snap[k] for k in now}
        out["compile_s"] = round(out["compile_s"], 1)
        return out

    def memory(self):
        stats = self.device.memory_stats() or {}
        return {"bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def one_device_mesh():
    """The one-chip legs pin one device so the script runs the same on a
    one-chip and a four-chip host."""
    import jax
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    return initialize_mesh(MeshLayout(), devices=jax.devices()[:1])


def release():
    """Drop the previous leg's device state before the next one allocates."""
    from deepspeed_tpu.parallel import mesh as mesh_mod

    mesh_mod.reset_mesh()
    gc.collect()


def train_config(micro_batch, zero_stage):
    # the memory-lean Adam of the benchmark's training cells
    # (benchmark/traffic/train-*.json): lr 1e-4, bf16 first moment, bf16
    # gradient accumulation.  No warm-up in so few steps, so the rate stays
    # low: at 3e-4 opt-1.3b's loss fell for three steps and jumped on the
    # fourth.
    return {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "mu_dtype": "bfloat16"}},
        "data_types": {"grad_accum_dtype": "bf16"},
        "zero_optimization": {"stage": zero_stage},
        "bf16": {"enabled": True},
        "steps_per_print": 10 ** 9,
    }


def train_steps(engine, vocab, seq, steps, meter, expect_mosaic):
    """>= 6 steps on one fixed seeded batch; returns the leg's record.
    ``expect_mosaic``: the lowered step must hold the compiled kernel."""
    import numpy as np

    batch = {"input_ids": np.random.default_rng(0).integers(
        0, vocab, (engine.train_batch_size, seq)).astype(np.int32)}
    # the kernel in the step really is the compiled one
    mosaic_calls = engine.lower_train_step(batch).as_text().count(
        "tpu_custom_call")
    if expect_mosaic:
        check(mosaic_calls > 0, "lowered train step holds no Mosaic "
              "custom call: attention did not take the flash kernel")
    losses, compiles_by_step = [], []
    for _ in range(steps):
        c0 = meter.compiles()
        losses.append(float(engine.train_batch(batch=batch)))
        compiles_by_step.append(meter.compiles() - c0)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    # the engine compiles one more step variant on its second call (the
    # first call's outputs arrive committed); after that, nothing
    check(sum(compiles_by_step[2:]) == 0,
          f"compiles after the second step: {compiles_by_step}")
    return {"steps": steps, "losses": [round(x, 4) for x in losses],
            "mosaic_custom_calls": mosaic_calls,
            "compiles_by_step": compiles_by_step}


def leg_train(sz, meter, on_tpu):
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM

    snap = meter.snapshot()
    model = CausalLM(sz["train_model"], attn_impl=sz["train_attn"],
                     **sz["train_overrides"])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=train_config(sz["train_micro_batch"], 1),
        mesh=one_device_mesh())
    rec = train_steps(engine, model.config.vocab_size, sz["train_seq"],
                      sz["train_steps"], meter, on_tpu)
    rec.update(model=sz["train_model"], params=model.param_count,
               seq_len=sz["train_seq"], micro_batch=sz["train_micro_batch"],
               **meter.since(snap), **meter.memory())
    return rec


def random_bf16_params(model, seed):
    """Random weights from a seed, born bf16: the cast fuses into the
    initialiser so the fp32 tree never sits on the device."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import init_params

    def init(rng):
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            init_params(model.config, rng))

    return jax.jit(init)(jax.random.PRNGKey(seed))


def make_stream(prefix, lengths, new_tokens, vocab, seed):
    import numpy as np
    from deepspeed_tpu.inference.serving import Request

    rng = np.random.default_rng(seed)
    lo, hi = new_tokens
    reqs = []
    for i, n in enumerate(lengths):
        # the first two requests share a length and a budget so one
        # generate() call can replay them (leg_serve's token count)
        budget = lo if i < 2 else int(rng.integers(lo, hi + 1))
        reqs.append(Request(
            rid=f"{prefix}{i}", max_new_tokens=budget,
            input_ids=rng.integers(0, vocab, (n,)).astype(np.int32)))
    return reqs


def check_stream(results, requests, what):
    by_rid = {r.rid: r for r in results}
    check(len(by_rid) == len(requests),
          f"{what}: {len(by_rid)} results for {len(requests)} requests")
    for req in requests:
        res = by_rid[req.rid]
        check(res.finish_reason in ("length", "eos"),
              f"{what}: {req.rid} finished {res.finish_reason!r}")
        check(len(res.output_ids) == req.max_new_tokens
              or res.finish_reason == "eos",
              f"{what}: {req.rid} emitted {len(res.output_ids)} of "
              f"{req.max_new_tokens} tokens")
    return by_rid


def check_health(sv, what):
    h = sv.health()
    check(h["quarantined_slots"] == 0, f"{what}: slots quarantined: {h}")
    check(h["pool_alive"], f"{what}: KV pool consumed")
    acct = sv.page_accounting()
    check(acct["balanced"], f"{what}: page accounting unbalanced: {acct}")
    return h


def serve_two_streams(sv, sz, vocab, meter):
    """Two seeded streams through ``run()``; the second must compile nothing
    and leave the program inventory as the first left it."""
    a = make_stream("a", sz["stream_a"], sz["new_tokens"], vocab, seed=1)
    b = make_stream("b", sz["stream_b"], sz["new_tokens"], vocab, seed=2)
    res_a = check_stream(sv.run(a), a, "stream a")
    buckets = sorted({r.prefill_bucket for r in res_a.values()})
    check(len(buckets) >= 3, f"stream a hit prefill buckets {buckets} only")
    inventory, c0 = sv.program_inventory(), meter.compiles()
    res_b = check_stream(sv.run(b), b, "stream b")
    recompiled = meter.compiles() - c0
    check(recompiled == 0, f"second stream compiled {recompiled} program(s)")
    check(sv.program_inventory() == inventory,
          f"inventory changed: {inventory} -> {sv.program_inventory()}")
    health = check_health(sv, "after two streams")
    return a, res_a, {
        "requests": len(a) + len(b),
        "tokens_out": sum(len(r.output_ids)
                          for r in (*res_a.values(), *res_b.values())),
        "prefill_buckets": buckets,
        "program_inventory": inventory,
        "second_stream_compiles": recompiled,
        "kv_pool_bytes_total": health["kv_pool_bytes_total"],
        "kv_pool_bytes_per_device": health["kv_pool_bytes_per_device"],
    }


def leg_serve(sz, meter):
    """Returns the leg's record and what the numerics leg reuses."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM

    snap = meter.snapshot()
    model = CausalLM(sz["serve_model"], **sz["serve_overrides"])
    engine = deepspeed_tpu.init_inference(
        model=model, params=random_bf16_params(model, seed=0), dtype="bf16",
        mesh=one_device_mesh())
    # plain serving(), not supervised_serving(): a supervisor that
    # warm-restarts would turn a device fault into a pass
    sv = engine.serving(b_slots=sz["b_slots"], page_size=sz["page_size"],
                        max_model_len=sz["max_model_len"])
    stream, results, rec = serve_two_streams(sv, sz, model.config.vocab_size,
                                             meter)
    rec.update(model=sz["serve_model"], params=model.param_count,
               **meter.since(snap), **meter.memory())
    # reported, not gated: random weights put many logits within one bf16
    # rounding of the maximum, so generate()'s contiguous-cache decode and
    # the paged engine may pick different tokens
    pair = stream[:2]
    out = np.asarray(engine.generate(
        np.stack([r.input_ids for r in pair]),
        max_new_tokens=pair[0].max_new_tokens))
    n_prompt = len(pair[0].input_ids)
    gen = [out[i, n_prompt:] for i in range(2)]
    rec["tokens_equal_to_generate"] = int(sum(
        int((g == results[r.rid].output_ids).sum())
        for g, r in zip(gen, pair)))
    rec["tokens_compared_to_generate"] = int(sum(len(g) for g in gen))
    del sv
    return rec, engine


def rel_err(got, ref):
    """max |got - ref| over max |ref|, computed in float32 on the host."""
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    check(np.isfinite(got).all(), "non-finite values")
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def numerics_flash(sz):
    """The train leg's attention call — same function, same shape — with the
    kernel and with the masked XLA path."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models.transformer import _attention

    cfg = get_config(sz["train_model"], **sz["train_overrides"])
    S, H, hd = sz["train_seq"], cfg.num_heads, cfg.dims_per_head
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(kk, (1, S, n, hd), jnp.bfloat16)
               for kk, n in zip(ks, (H, cfg.kv_heads, cfg.kv_heads)))
    w = jax.random.normal(ks[3], (1, S, H, hd), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)[None]

    def fwd_and_grads(impl, dtype):
        def f(q, k, v):
            return _attention(cfg, q, k, v, pos, attn_impl=impl)

        def loss(q, k, v):
            return (f(q, k, v).astype(jnp.float32) * w).sum()

        args = tuple(x.astype(dtype) for x in (q, k, v))
        return (jax.jit(f)(*args),
                *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args))

    got = fwd_and_grads("pallas", jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        ref = fwd_and_grads("xla", jnp.float32)
    errs = {name: rel_err(g, r)
            for name, g, r in zip(("out", "dq", "dk", "dv"), got, ref)}
    for name, e in errs.items():
        check(e <= FLASH_REL_TOL,
              f"flash {name}: rel err {e:.4g} > {FLASH_REL_TOL:.4g}")
    return {"shape": [1, S, H, hd], "tol": FLASH_REL_TOL,
            **{f"{n}_rel_err": round(e, 5) for n, e in errs.items()}}


def numerics_paged(sz, engine):
    """One prompt: paged prefill, then teacher-forced decode steps, against
    the plain forward over the whole sequence.  Logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, params = engine.model, engine.params
    P, D, ps = sz["parity_prompt"], sz["parity_decode"], sz["page_size"]
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, model.config.vocab_size, (1, P + D)).astype(np.int32))
    forward = jax.jit(model.apply_fn)
    ref = np.asarray(forward(params, toks)[0], np.float32)
    other = toks.at[:, :ps].set((toks[:, :ps] + 1) % model.config.vocab_size)
    one_page = rel_err(forward(params, other)[0, ps:], ref[ps:])
    tol = PAGED_TOL_OF_ONE_PAGE * one_page

    n_pages = -(-(P + D) // ps)
    cache = model.init_paged_cache(1 + n_pages, ps, dtype=jnp.bfloat16)
    table = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)[None]   # page 0: trash
    step = jax.jit(model.apply_paged)
    s_pad = n_pages * ps
    prompt = jnp.zeros((1, s_pad), jnp.int32).at[:, :P].set(toks[:, :P])
    mask = (jnp.arange(s_pad) < P)[None]
    logits, cache = step(params, prompt, cache, table,
                         jnp.zeros((1,), jnp.int32), mask)
    errs = {"prefill": rel_err(logits[0, :P], ref[:P])}
    worst = 0.0
    for i in range(D):
        logits, cache = step(params, toks[:, P + i:P + i + 1], cache, table,
                             jnp.full((1,), P + i, jnp.int32),
                             jnp.ones((1, 1), bool))
        worst = max(worst, rel_err(logits[0, 0], ref[P + i]))
    errs["decode"] = worst
    for name, e in errs.items():
        check(e <= tol, f"paged {name}: rel err {e:.4g} > {tol:.4g} "
              f"(a quarter of one page of context, {one_page:.4g})")
    return {"prompt": P, "decode_steps": D,
            "one_page_of_context_rel": round(one_page, 5),
            **{f"{n}_rel_err": round(e, 5) for n, e in errs.items()}}


def leg_zero3(sz, meter, on_tpu):
    """ZeRO-3 over dp=4.  At 14-16 bytes a parameter opt-1.3b's training
    state cannot fit one 16 GB chip, so a finite falling loss is itself the
    proof of partitioning; the shard check says where the bytes are."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    snap = meter.snapshot()
    devices = jax.devices()[:4]
    model = CausalLM(sz["zero3_model"], **sz["zero3_overrides"])
    config = train_config(1, 3)
    if sz["zero3_persistence_threshold"] is not None:
        config["zero_optimization"]["stage3_param_persistence_threshold"] = \
            sz["zero3_persistence_threshold"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=config,
        mesh=initialize_mesh(MeshLayout(dp=4), devices=devices))
    rec = train_steps(engine, model.config.vocab_size, sz["zero3_seq"],
                      sz["zero3_steps"], meter,
                      # S=2048 resolves to the flash kernel too, per shard
                      on_tpu and sz["zero3_seq"] >= 2048)
    total, per_device = 0, {d.id: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(
            (engine.state.master_params, engine.state.opt_state)):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] += shard.data.nbytes
    worst = max(per_device.values()) / total
    # a quarter, plus the small leaves under the persistence threshold
    # (norm scales, biases) that ZeRO-3 keeps whole on every device
    check(worst <= 0.27, f"a device holds {worst:.3f} of master+optimizer "
          f"bytes: {per_device} of {total}")
    rec.update(model=sz["zero3_model"], params=model.param_count,
               seq_len=sz["zero3_seq"], state_bytes_total=total,
               state_share_worst_device=round(worst, 4),
               **meter.since(snap), **meter.memory())
    return rec


def leg_serve_tp4(sz, meter):
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.parallel.mesh import initialize_serving_mesh

    snap = meter.snapshot()
    model = CausalLM(sz["serve_model"], **sz["serve_overrides"])
    mesh = initialize_serving_mesh(tp=4, n_devices=4)
    engine = deepspeed_tpu.init_inference(
        model=model, params=random_bf16_params(model, seed=0), dtype="bf16",
        tensor_parallel={"tp_size": 4}, mesh=mesh)
    sv = engine.serving(b_slots=sz["b_slots"], page_size=sz["page_size"],
                        max_model_len=sz["max_model_len"])
    _, _, rec = serve_two_streams(sv, sz, model.config.vocab_size, meter)
    check(rec["kv_pool_bytes_per_device"] * 4 == rec["kv_pool_bytes_total"],
          f"KV pool not split four ways: {rec['kv_pool_bytes_per_device']} "
          f"per device of {rec['kv_pool_bytes_total']}")
    rec.update(model=sz["serve_model"], **meter.since(snap), **meter.memory())
    return rec


def native_ops_loaded():
    """Shared objects of the repo's native op builder mapped into this
    process.  The main path must load none: the chip machine holds only what
    git commits, and ``ops/csrc/build/`` is not that."""
    with open("/proc/self/maps") as f:
        return sorted({line.split()[-1] for line in f
                       if "/cpu_adam_" in line or "/async_io_" in line})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU with interpret-mode kernels; "
                         "the summary says platform cpu; no verdict line")
    args = ap.parse_args()
    if args.rehearse_cpu:
        # before jax is imported; all three are asked for by name
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DS_TPU_PALLAS_INTERPRET"] = "1"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    import jax

    t_start = time.monotonic()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print(f"chip_smoke: no TPU: jax.devices()[0] is {dev!r}. This script "
              "checks the chip; it does not fall back.", file=sys.stderr)
        return 1
    if on_tpu and args.rehearse_cpu:
        print("chip_smoke: --rehearse-cpu but JAX holds a TPU", file=sys.stderr)
        return 1

    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()       # before anything compiles
    summary = {
        "device": device,
        "versions": {"jax": jax.__version__,
                     "jaxlib": importlib.metadata.version("jaxlib"),
                     "libtpu": importlib.metadata.version("libtpu"),
                     "python": sys.version.split()[0]},
        "compile_cache_dir": cache_dir,
        "legs": {"gate": "passed"},
    }
    sz = FULL if on_tpu else REHEARSAL
    meter = Meter(dev)
    legs = summary["legs"]

    # a leg that returns has passed: every gate in it raises
    passed = {"status": "passed"}
    legs["train"] = {**passed, **leg_train(sz, meter, on_tpu)}
    release()
    serve, engine = leg_serve(sz, meter)
    legs["serve"] = {**passed, **serve}
    legs["numerics"] = {**passed,
                        "paged_vs_forward": numerics_paged(sz, engine)}
    del engine
    release()
    legs["numerics"]["flash_vs_xla"] = numerics_flash(sz)
    if device["count"] >= 4:
        legs["zero3_dp4"] = {**passed, **leg_zero3(sz, meter, on_tpu)}
        release()
        legs["serve_tp4"] = {**passed, **leg_serve_tp4(sz, meter)}
        release()
    else:
        legs["zero3_dp4"] = legs["serve_tp4"] = (
            f"not run: {device['count']} device(s)")

    build_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "deepspeed_tpu", "ops", "csrc", "build")
    built = sorted(os.listdir(build_dir)) if os.path.isdir(build_dir) else []
    loaded = native_ops_loaded()
    check(not loaded and not built,
          f"native ops on the main path: loaded {loaded}, built {built}")
    summary["native_ops_loaded"] = len(loaded)
    summary["wall_s"] = round(time.monotonic() - t_start, 1)   # set-up info
    if not on_tpu:
        summary["rehearsal"] = ("CPU, toy sizes, interpret-mode kernels: "
                                "control flow only")
    summary["claim"] = None
    print(json.dumps(summary))
    if on_tpu:
        # the verdict: exactly these keys, the device as JAX reports it
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
