"""Traffic kind ``serve-openloop``: ``init_inference`` -> ``engine.serving``
-> ``run(requests)`` under seeded open-loop arrivals at the fixed rate
written in the traffic file.

The engine's own loop is the load generator: ``run`` admits a request once
its ``arrival_time`` is due, and every latency is counted from that due
time (``RequestResult.arrival_s``), so a stall is charged to the requests
that waited behind it.  The window is the ``--seconds`` after ``run``
starts; every request is due inside it, and ``run`` then drains, bounded.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.lib import arrivals, reference, stats, system

# Paged prefill + decode against the float32 reference's full forward, on
# logits, as max|diff| / max|ref|.  PR 21 measured 0.014-0.015 for the bf16
# paged path at these widths and 0.535 for one misplaced page of context.
# 0.05 is three times the rounding and a tenth of a lost page.
LOGITS_REL_TOL = 0.05
FINISHED = ("length", "eos")


def parity_paged(model, params, page_size: int, n_prompt: int, n_decode: int,
                 seed: int) -> Dict[str, float]:
    """One seeded prompt through ``apply_paged``: whole-prompt prefill, then
    ``n_decode`` teacher-forced decode steps through the paged cache; logits
    against the plain reference's forward over the whole sequence."""
    import jax
    import jax.numpy as jnp

    cfg = model.config
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (1, n_prompt + n_decode)).astype(np.int32))
    ref = np.asarray(reference.reference_logits(cfg, params, toks[0]))
    n_pages = -(-(n_prompt + n_decode) // page_size)
    cache = model.init_paged_cache(1 + n_pages, page_size, dtype=jnp.bfloat16)
    table = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)[None]  # page 0: trash
    step = jax.jit(model.apply_paged)
    s_pad = n_pages * page_size
    prompt = jnp.zeros((1, s_pad), jnp.int32).at[:, :n_prompt].set(
        toks[:, :n_prompt])
    mask = (jnp.arange(s_pad) < n_prompt)[None]
    logits, cache = step(params, prompt, cache, table,
                         jnp.zeros((1,), jnp.int32), mask)
    out = {"prefill_rel_err": reference.rel_err(logits[0, :n_prompt],
                                                ref[:n_prompt])}
    worst = 0.0
    for i in range(n_decode):
        logits, cache = step(params, toks[:, n_prompt + i:n_prompt + i + 1],
                             cache, table,
                             jnp.full((1,), n_prompt + i, jnp.int32),
                             jnp.ones((1, 1), bool))
        worst = max(worst, reference.rel_err(logits[0, 0], ref[n_prompt + i]))
    out["decode_rel_err"] = worst
    return out


def tokens_inside(results, t_end: float) -> float:
    """Output tokens emitted by ``t_end``.  A result carries the stamps of
    its first and last token; the tokens between come one per decode tick,
    so they are placed evenly between the two stamps."""
    total = 0.0
    for r in results:
        n = len(r.output_ids)
        if n == 0 or r.first_token_s > t_end:
            continue
        if r.finish_s <= t_end or n == 1:
            total += n
        else:
            share = (t_end - r.first_token_s) / (r.finish_s - r.first_token_s)
            total += 1 + int((n - 1) * share)
    return total


def run(ctx) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request, ServeTimeout
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    t, args = ctx.traffic, ctx.args
    cfg = system.transformer_config(ctx.config, args.rehearse)
    model = CausalLM(cfg)
    engine = deepspeed_tpu.init_inference(
        model=model, params=system.random_bf16_params(cfg, args.seed),
        dtype="bf16",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))
    geo = t["engine"]
    par = parity_paged(model, engine.params, geo["page_size"],
                       t["parity"]["prompt"], t["parity"]["decode"], args.seed)
    checks = {"logits_match_reference":
              max(par.values()) <= LOGITS_REL_TOL}
    ctx.note(parity=par, tol=LOGITS_REL_TOL)

    # plain serving(), not supervised_serving(): a supervisor that
    # warm-restarts would turn a device fault into a pass
    sv = engine.serving(**geo)
    schedule = arrivals.open_loop_schedule(t, args.seconds)
    rng = np.random.default_rng(args.seed)

    def request(rid, arrival, n_prompt, n_out):
        return Request(rid=rid, arrival_time=arrival, max_new_tokens=n_out,
                       input_ids=rng.integers(0, cfg.vocab_size, (n_prompt,))
                       .astype(np.int32))

    requests = [request(f"r{i}", a, p, o)
                for i, (a, p, o) in enumerate(schedule)]
    # warm the decode program and the prefill programs this schedule hits,
    # and no others: prompt lengths in rising order, skipping every length
    # the last warmed program already pads to (RequestResult.prefill_bucket)
    covered = 0
    for n in sorted({p for _, p, _ in schedule}):
        if n > covered:
            (warm,) = sv.run([request(f"warm{n}", 0.0, n, 3)])
            covered = warm.prefill_bucket
    inventory = sv.program_inventory()

    max_ticks = int((args.seconds + t["drain_seconds"]) / 0.02)
    ctx.start_window(trace_units=t["trace_ticks"])
    try:
        results = sv.run(requests, max_ticks=max_ticks)
    except ServeTimeout:
        results = sv.take_results()
    ctx.end_window()

    done = [r for r in results if r.finish_reason in FINISHED]
    asked = {q.rid: q.max_new_tokens for q in requests}
    checks["every_finished_request_has_its_tokens"] = all(
        len(r.output_ids) == asked[r.rid] for r in done)
    checks["no_request_failed"] = len(done) == len(requests)
    checks["pages_balanced"] = bool(sv.page_accounting()["balanced"])
    health = sv.health()
    checks["no_slot_quarantined"] = (health["quarantined_slots"] == 0
                                     and bool(health["pool_alive"]))
    checks["inventory_unchanged"] = sv.program_inventory() == inventory

    end_to_end: Dict[str, float] = {}
    serve: Dict[str, Any] = {"results": results, "cfg": cfg}
    if results:
        first = requests[int(results[0].rid[1:])]
        t0 = results[0].arrival_s - first.arrival_time
        gaps: List[float] = [
            (r.finish_s - r.first_token_s) / (len(r.output_ids) - 1)
            for r in done if len(r.output_ids) > 1]
        ttft = [r.ttft_s for r in done]
        end_to_end = {
            "serve_tokens_per_s": tokens_inside(done, t0 + args.seconds)
            / args.seconds,
            "tpot_p50_ms": stats.median(gaps) * 1e3,
        }
        ctx.note(requests=len(requests), finished=len(done),
                 ttft_p50_ms=stats.median(ttft) * 1e3,
                 ttft_p90_ms=stats.percentile(ttft, 0.90) * 1e3,
                 queue_wait_p90_ms=stats.percentile(
                     [r.queued_s for r in done], 0.9) * 1e3,
                 queue_at_window_end=sum(
                     1 for r in done if r.admit_s > t0 + args.seconds),
                 queued_token_share_at_window_end=sum(
                     asked[r.rid] for r in done
                     if r.admit_s > t0 + args.seconds) / sum(asked.values()),
                 tpot_p50_ms=end_to_end["tpot_p50_ms"],
                 completed_tokens_per_s=end_to_end["serve_tokens_per_s"],
                 drain_s=max(r.finish_s for r in results) - t0 - args.seconds,
                 offered_tokens_per_s=sum(asked.values()) / args.seconds)
    return {
        "checks": checks,
        "attempted": len(requests),
        "failed": len(requests) - len(done),
        "end_to_end": end_to_end,
        "serve": serve,
    }
