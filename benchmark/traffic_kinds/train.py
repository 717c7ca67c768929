"""Traffic kind ``train``: ``deepspeed_tpu.initialize`` + ``train_batch`` on
seeded random token batches, timed step by step.

The loop keeps stepping until ``--seconds`` have passed AND ``min_steps``
steps are done, always finishing the step in flight; each call of
``train_batch`` ends in a fetch of ``grad_norm``, so the step has ended on
the device.  The judged rate is all the tokens of those N whole steps over
all the time from the first step's start to the last step's end.  Nothing
here counts work inside a fixed window.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from benchmark.lib import reference, stats, system

# bf16 forward of the system against the float32 reference on the same
# bf16-valued weights, logits, as max|diff| / max|ref|.  bf16 keeps 8
# significant bits; through 10-24 layers PR 21 measured 0.014-0.015 between
# two bf16 formulations at these widths.  0.05 is three times that and a
# tenth of what one misplaced page of context costs (0.535): a dropped
# term, a wrong mask or rotary convention lands at order 0.3-1.
LOGITS_REL_TOL = 0.05
PARITY_TOKENS = 256


def _engine_config(traffic: Dict[str, Any], seed: int) -> Dict[str, Any]:
    conf = dict(traffic["engine_config"])
    conf["train_micro_batch_size_per_gpu"] = traffic["micro_batch"]
    conf["zero_optimization"] = {"stage": traffic["zero_stage"],
                                 **traffic.get("engine_config_extra", {})
                                 .get("zero_optimization", {})}
    conf["steps_per_print"] = 10 ** 9
    conf["seed"] = system.jax_seed(seed)
    return conf


def parity(cfg, attn_impl: str, seed: int) -> Dict[str, float]:
    """Model forward vs the plain reference on one seeded sequence, at the
    widths of the cell, before the engine takes the memory."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import CausalLM

    n = min(PARITY_TOKENS, cfg.max_seq_len)
    params = system.random_bf16_params(cfg, seed)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (1, n)).astype(np.int32))
    model = CausalLM(cfg, attn_impl=attn_impl)
    got = jax.jit(model.apply_fn)(params, toks)[0]
    ref = reference.reference_logits(cfg, params, toks[0])
    return {"logits_rel_err": reference.rel_err(got, ref), "tokens": n}


def run(ctx) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    t, args = ctx.traffic, ctx.args
    cfg = system.transformer_config(ctx.config, args.rehearse)
    S, dp = t["seq_len"], t["dp"]
    checks = {}
    par = parity(cfg, t["attn_impl"], args.seed)
    checks["logits_match_reference"] = par["logits_rel_err"] <= LOGITS_REL_TOL
    ctx.note(parity=par, tol=LOGITS_REL_TOL)

    model = CausalLM(cfg, attn_impl=t["attn_impl"])
    layout = MeshLayout(dp=dp) if dp > 1 else MeshLayout()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=_engine_config(t, args.seed),
        mesh=initialize_mesh(layout, devices=jax.devices()[:dp]))
    rng = np.random.default_rng(args.seed)
    batches = [{"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size, S)).astype(np.int32)}
        for _ in range(t["distinct_batches"])]
    tokens_per_step = engine.train_batch_size * S

    losses = []
    for i in range(t["warmup_steps"]):
        losses.append(engine.train_batch(batch=batches[i % len(batches)]))
    first_loss = float(losses[0])

    ctx.start_window(trace_units=t["trace_steps"])
    step_s, skipped0 = [], engine.skipped_steps
    t_end = time.monotonic() + args.seconds
    i = t["warmup_steps"]
    t_first = time.perf_counter()
    while time.monotonic() < t_end or len(step_s) < t["min_steps"]:
        t0 = time.perf_counter()
        loss = engine.train_batch(batch=batches[i % len(batches)])
        step_s.append(time.perf_counter() - t0)
        losses.append(loss)
        i += 1
    elapsed_s = time.perf_counter() - t_first
    ctx.end_window()

    window_losses = [float(x) for x in losses[t["warmup_steps"]:]]
    ctx.note(first_loss=first_loss, losses=[round(x, 4) for x in window_losses])
    bad = sum(1 for x in window_losses if not np.isfinite(x))
    skipped = engine.skipped_steps - skipped0
    checks["losses_finite"] = bad == 0
    checks["loss_fell"] = float(np.mean(window_losses[-5:])) < window_losses[0]
    rate = stats.whole_step_rate(len(step_s), tokens_per_step, elapsed_s, dp)
    ctx.note(steps=len(step_s), elapsed_s=elapsed_s,
             whole_step_tokens_per_s_chip=rate,
             median_step_tokens_per_s_chip=stats.median_step_rate(
                 step_s, tokens_per_step, dp),
             step_ms_p50=stats.median(step_s) * 1e3,
             step_ms_max=max(step_s) * 1e3,
             between_steps_ms=(elapsed_s - sum(step_s)) * 1e3)
    return {
        "checks": checks,
        "attempted": len(step_s), "failed": bad + skipped,
        "end_to_end": {"train_tokens_per_s_chip": rate},
        "train": {"step_s": step_s, "elapsed_s": elapsed_s,
                  "tokens_per_step": tokens_per_step, "chips": dp, "seq_len": S, "micro_batch": t["micro_batch"],
                  "cfg": cfg, "remat": bool(cfg.remat)},
    }
