"""Traffic kind ``serve-backlog``: ``init_inference`` -> ``engine.serving``
-> ``run(requests)`` with every request due at t = 0.

The cell above the knee: the queue never empties inside the window, every
slot is full in every tick, and the end-to-end metric is the output tokens
the engine completes per second (``serve_tokens_per_s``): each output
token whose own stamp (``RequestResult.token_s``) falls in the
``--seconds`` after ``run`` starts, over the window.  ``run`` then drains,
bounded, and every request has to finish: the backlog is sized in the
traffic file to about 1.5 x what the window completes, not to infinity.
Latencies are a ``note`` here, not metrics: with the whole backlog due at
once a request's TTFT is its place in the queue.

The plain reference is the module the configuration file names under
``reference`` (default ``benchmark.lib.reference``): ``reference_logits``,
``rel_err``, and ``layer_checks`` if it has one (single layers of the
system against the reference's, each with its own tolerance).
"""
from __future__ import annotations

import importlib
from typing import Any, Dict

import numpy as np

from benchmark.lib import arrivals, stats, system

# Paged prefill + decode against the float32 reference's full forward, on
# logits, as max|diff| / max|ref|: serve-openloop's check and its limit
# (0.014-0.015 measured for the dense bf16 paged path, 0.535 for one
# misplaced page: PERF.md, PR 21).  The OLMoE block measured 0.0082-0.0162 at
# the published widths, 0.053-0.074 without its QK-norm and 0.19-0.20 with
# renormalised gates (PERF.md, PR 26); what this limit cannot see (top-7
# routing moves the logits by 0.017-0.022) the reference's ``layer_checks``
# hold.
LOGITS_REL_TOL = 0.05
FINISHED = ("length", "eos")


def parity_paged(ref, model, params, page_size: int, n_prompt: int,
                 n_decode: int, seed: int) -> Dict[str, float]:
    """One seeded prompt through ``apply_paged``: whole-prompt prefill (the
    prompt padded to whole pages, the padding masked), then ``n_decode``
    teacher-forced decode steps through the paged cache; logits against
    ``ref.reference_logits`` over the whole sequence."""
    import jax
    import jax.numpy as jnp

    cfg = model.config
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (1, n_prompt + n_decode)).astype(np.int32))
    want = np.asarray(ref.reference_logits(cfg, params, toks[0]))
    n_pages = -(-(n_prompt + n_decode) // page_size)
    cache = model.init_paged_cache(1 + n_pages, page_size, dtype=jnp.bfloat16)
    table = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)[None]  # page 0: trash
    step = jax.jit(model.apply_paged)
    s_pad = n_pages * page_size
    prompt = jnp.zeros((1, s_pad), jnp.int32).at[:, :n_prompt].set(
        toks[:, :n_prompt])
    logits, cache = step(params, prompt, cache, table,
                         jnp.zeros((1,), jnp.int32),
                         (jnp.arange(s_pad) < n_prompt)[None])
    out = {"prefill_rel_err": ref.rel_err(logits[0, :n_prompt],
                                          want[:n_prompt])}
    worst = 0.0
    for i in range(n_decode):
        logits, cache = step(params, toks[:, n_prompt + i:n_prompt + i + 1],
                             cache, table,
                             jnp.full((1,), n_prompt + i, jnp.int32),
                             jnp.ones((1, 1), bool))
        worst = max(worst, ref.rel_err(logits[0, 0], want[n_prompt + i]))
    out["decode_rel_err"] = worst
    return out


def run(ctx) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request, ServeTimeout
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

    t, args = ctx.traffic, ctx.args
    ref = importlib.import_module(
        ctx.config.get("reference", "benchmark.lib.reference"))
    cfg = system.transformer_config(ctx.config, args.rehearse)
    model = CausalLM(cfg)
    engine = deepspeed_tpu.init_inference(
        model=model, params=system.random_bf16_params(cfg, args.seed),
        dtype="bf16",
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))
    geo = t["engine"]
    par = parity_paged(ref, model, engine.params, geo["page_size"],
                       t["parity"]["prompt"], t["parity"]["decode"], args.seed)
    checks = {"logits_match_reference": max(par.values()) <= LOGITS_REL_TOL}
    ctx.note(parity=par, tol=LOGITS_REL_TOL)
    if hasattr(ref, "layer_checks"):
        layers = ref.layer_checks(cfg, engine.params, args.seed)
        checks["layers_match_reference"] = all(
            c["rel_err"] <= c["tol"] for c in layers.values())
        ctx.note(layer_checks=layers)

    # plain serving(), not supervised_serving(): a supervisor that
    # warm-restarts would turn a device fault into a pass
    sv = engine.serving(**geo)
    sizes = np.random.default_rng(t["size_seed"])
    prompts = arrivals.draw_lengths(t["prompt_tokens"], t["n_requests"], sizes)
    outputs = arrivals.draw_lengths(t["output_tokens"], t["n_requests"], sizes)
    rng = np.random.default_rng(args.seed)

    def request(rid, n_prompt, n_out):
        return Request(rid=rid, arrival_time=0.0, max_new_tokens=int(n_out),
                       input_ids=rng.integers(
                           0, cfg.vocab_size, (int(n_prompt),)).astype(np.int32))

    requests = [request(f"r{i}", p, o)
                for i, (p, o) in enumerate(zip(prompts, outputs))]
    # warm the decode program and the prefill programs this backlog hits,
    # and no others: prompt lengths in rising order, skipping every length
    # the last warmed program already pads to (RequestResult.prefill_bucket)
    covered = 0
    for n in sorted({int(p) for p in prompts}):
        if n > covered:
            (warm,) = sv.run([request(f"warm{n}", n, 3)])
            covered = warm.prefill_bucket
    inventory = sv.program_inventory()

    max_ticks = int((args.seconds + t["drain_seconds"]) / 0.002)
    ctx.start_window(trace_units=t["trace_ticks"])
    try:
        results = sv.run(requests, max_ticks=max_ticks)
    except ServeTimeout:
        results = sv.take_results()
    ctx.end_window()

    done = [r for r in results if r.finish_reason in FINISHED]
    asked = {q.rid: q.max_new_tokens for q in requests}
    checks["every_finished_request_has_its_tokens"] = all(
        len(r.output_ids) == asked[r.rid] == len(r.token_s) for r in done)
    checks["no_request_failed"] = len(done) == len(requests)
    checks["pages_balanced"] = bool(sv.page_accounting()["balanced"])
    health = sv.health()
    checks["no_slot_quarantined"] = (health["quarantined_slots"] == 0
                                     and bool(health["pool_alive"]))
    checks["inventory_unchanged"] = sv.program_inventory() == inventory

    end_to_end: Dict[str, float] = {}
    serve: Dict[str, Any] = {"results": results, "cfg": cfg}
    if results:
        # every request is due the moment run() starts
        t_end = min(r.arrival_s for r in results) + args.seconds
        serve["t_end"] = t_end      # on the spans' clock: readers of the
        # full-slot regime leave the drain's emptying ticks out
        inside = sum(1 for r in done for s in r.token_s if s <= t_end)
        unfinished = sum(1 for r in results if r.finish_s > t_end)
        gaps = [(r.finish_s - r.first_token_s) / (len(r.output_ids) - 1)
                for r in done if len(r.output_ids) > 1]
        ttft = [r.ttft_s for r in done]
        end_to_end = {"serve_tokens_per_s": inside / args.seconds}
        # where a run's rate came from: the same rate over each quarter of
        # the window (a run-long shift moves all four, a stall one), and the
        # time from one decode tick's stamp to the next
        t_start = t_end - args.seconds
        stamps = np.asarray([s for r in done for s in r.token_s if s <= t_end])
        quarters = np.histogram(stamps, bins=4, range=(t_start, t_end))[0]
        ticks = np.diff(np.unique(np.asarray(
            [s for r in done for s in r.token_s[1:] if s <= t_end]))) * 1e3
        ctx.note(requests=len(requests), finished=len(done),
                 tokens_inside_window=inside,
                 tokens_in_backlog=int(sum(asked.values())),
                 unfinished_at_window_end=unfinished,
                 queued_at_window_end=sum(
                     1 for r in results if r.admit_s > t_end),
                 window_quarters_tokens_per_s=[
                     float(q) * 4 / args.seconds for q in quarters],
                 tick_ms_p10_p50_p90=[
                     float(np.percentile(ticks, q)) for q in (10, 50, 90)]
                 if len(ticks) else None,
                 lookahead_launched_dropped=[
                     health.get("lookahead_launched_total"),
                     health.get("lookahead_dropped_total")],
                 tpot_p50_ms=stats.median(gaps) * 1e3,
                 ttft_p50_ms=stats.median(ttft) * 1e3,
                 ttft_p90_ms=stats.percentile(ttft, 0.90) * 1e3,
                 drain_s=max(r.finish_s for r in results) - t_end)
        if unfinished < geo["b_slots"]:
            ctx.note(backlog_too_small=(
                f"{unfinished} request(s) unfinished at the window's end, "
                f"fewer than the {geo['b_slots']} slots: the slots were not "
                "all full to the end and n_requests needs re-sizing"))
    return {
        "checks": checks,
        "attempted": len(requests),
        "failed": len(requests) - len(done),
        "end_to_end": end_to_end,
        "serve": serve,
    }
