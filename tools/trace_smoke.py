"""Observability smoke: assert the exported trace is real and the disabled
tracer is free.

Runs a tiny supervised stack end to end with tracing enabled — a couple of
real ``train_batch`` steps (SimpleModel on the virtual CPU mesh) plus a
short serving stream — then validates the Chrome/Perfetto export:

- the artifact is valid JSON in trace-event format;
- the expected span names from both paths are present (``train.batch``,
  ``train.data``, ``train.step``, ``train.fetch``, ``train.monitor``,
  ``serve.tick``, ``serve.admit``, ``serve.prefill``, ``serve.publish``,
  ``serve.decode``, ``serve.launch``, ``serve.fetch``, ``serve.emit``,
  ``serve.gauges``);
- nesting is sane: every recorded depth is non-negative, every duration is
  non-negative, and within each thread child spans lie inside their
  parents' intervals (events sorted by ts must nest like balanced
  brackets).

It also MEASURES the disabled-tracer cost — the exact call instrumentation
sites make (``trace_span(...)`` enter/exit) timed over many iterations with
tracing off — and reports it as ``disabled_span_ns``, and the same for the
calls the two per-program sites make (``serve.launch``, ``serve.fetch``:
``disabled_site_ns``).  That number is the
overhead guarantee docs/OBSERVABILITY.md quotes: the serving tick loop runs
3-4 such calls per tick against a device call measured in milliseconds.

Wired into tier-1 via tests/unit/test_observability.py::test_trace_smoke_tool
(in-process, CPU-only).  Exits nonzero on violation.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "tests"))

EXPECTED_SPANS = ("train.batch", "train.data", "train.step", "train.fetch",
                  "train.monitor", "serve.tick", "serve.admit",
                  "serve.prefill", "serve.publish", "serve.decode",
                  "serve.launch", "serve.fetch", "serve.emit",
                  "serve.gauges")


# the per-program sites of the serving loop, with the attrs they pass
# (inference/serving.py ``_launch_decode`` / ``_fetch``): a tick pays each once
DISABLED_SITES = {
    "overhead.probe": lambda i: {"tick": i},
    "serve.launch": lambda i: {"program": "decode", "seq": i, "ahead": 1},
    "serve.fetch": lambda i: {"program": "decode", "seq": i},
}


def measure_disabled_span_ns(iters: int = 200_000,
                             site: str = "overhead.probe") -> float:
    """ns per disabled ``with trace_span(...)`` — the instrumentation-site
    cost when tracing is off (must be noise against a device call), for
    the call one of ``DISABLED_SITES`` makes."""
    from deepspeed_tpu.observability import configure_tracer, trace_span

    configure_tracer(enabled=False)
    attrs = DISABLED_SITES[site]
    t0 = time.perf_counter()
    for i in range(iters):
        with trace_span(site, **attrs(i)):
            pass
    dt = time.perf_counter() - t0
    return dt / iters * 1e9


def validate_trace(doc: dict) -> list:
    """Trace-event sanity: returns a list of violation strings (empty =
    ok).  Nesting check: per (pid, tid), complete events sorted by start
    must close like balanced brackets — a child ends within its parent."""
    problems = []
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        return ["traceEvents missing or empty"]
    spans = [e for e in events if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    for want in EXPECTED_SPANS:
        if want not in names:
            problems.append(f"expected span {want!r} missing from trace")
    by_tid = {}
    for e in spans:
        if e.get("dur", 0) < 0:
            problems.append(f"negative duration on {e['name']!r}")
        by_tid.setdefault((e.get("pid"), e.get("tid")), []).append(e)
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        eps = 50.0   # µs slack: enter/exit stamps are host clock reads
        for e in evs:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"]:
                stack.pop()
            if stack:
                parent = stack[-1]
                if (e["ts"] + e["dur"]
                        > parent["ts"] + parent["dur"] + eps):
                    problems.append(
                        f"span {e['name']!r} overflows its enclosing "
                        f"{parent['name']!r} on tid {tid}")
            stack.append(e)
    return problems


def _histogram_slo_phase(prom: str) -> list:
    """Histogram + SLO coverage over the traced run's span history
    (ISSUE 12): serve.tick quantiles live and monotone, real histogram
    families on the exposition, and one SloRule driven to firing and back
    with its dstpu_alert{rule=...} gauge following."""
    from deepspeed_tpu.monitor import InMemoryMonitor
    from deepspeed_tpu.observability import (SloEvaluator, SloRule,
                                             get_tracer, prometheus_text)

    problems = []
    tracer = get_tracer()
    qs = [tracer.span_quantile("serve.tick", q)
          for q in (0.1, 0.5, 0.9, 0.99)]
    if any(v is None for v in qs):
        problems.append("serve.tick duration histogram missing")
    elif not all(a <= b for a, b in zip(qs, qs[1:])):
        problems.append(f"serve.tick quantiles not monotone: {qs}")
    if "dstpu_span_duration_seconds_bucket" not in prom:
        problems.append("prometheus exposition missing span histograms")

    mon = InMemoryMonitor()
    ev = SloEvaluator([
        SloRule.parse("slo/probe_depth < 4", name="probe_depth"),
        SloRule.parse("serve.tick p99 < 120", name="tick_p99"),
    ])
    mon.write_events([("slo/probe_depth", 9.0, 1)])   # violate
    ev.evaluate(monitor=mon, tracer=tracer)
    fired = ev.firing()
    text_fired = prometheus_text(monitor=_with_alerts(mon, ev, 1),
                                 tracer=tracer)
    mon.write_events([("slo/probe_depth", 1.0, 2)])   # satisfy
    ev.evaluate(monitor=mon, tracer=tracer)
    cleared = ev.firing()
    text_cleared = prometheus_text(monitor=_with_alerts(mon, ev, 2),
                                   tracer=tracer)
    if fired != ["probe_depth"]:
        problems.append(f"SLO rule did not fire as expected: {fired}")
    if cleared:
        problems.append(f"SLO rule did not clear: {cleared}")
    if 'dstpu_alert{rule="probe_depth"} 1' not in text_fired:
        problems.append("firing alert gauge missing from exposition")
    if 'dstpu_alert{rule="probe_depth"} 0' not in text_cleared:
        problems.append("cleared alert gauge missing from exposition")
    return problems


def _with_alerts(mon, ev, step):
    """Mirror the serving engine's wiring: firing states ride the monitor
    as alert{rule=...} gauges so the exposition renders dstpu_alert."""
    mon.write_events(ev.gauge_events(step))
    return mon


def run_smoke(trace_path: str = None, train_steps: int = 2,
              n_requests: int = 3, seed: int = 0) -> dict:
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.observability import (configure_tracer, get_tracer,
                                             prometheus_text,
                                             write_chrome_trace)
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from unit.simple_model import SimpleModel, make_config, random_batch

    configure_tracer(enabled=True, capacity=16384)
    try:
        # ---- train: two real fused steps on the virtual mesh
        mesh_mod.reset_mesh()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(16), config=make_config(batch_size=16))
        for s in range(train_steps):
            engine.train_batch(batch=random_batch(16, 16, seed=s))

        # ---- serve: a short mixed-length stream
        model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
        params = model.init_fn(jax.random.PRNGKey(0))
        ieng = deepspeed_tpu.init_inference(
            model=model, config={"dtype": "float32"}, params=params)
        serve = ieng.serving(b_slots=2, page_size=16, max_model_len=64)
        rng = np.random.default_rng(seed)
        reqs = [Request(rid=i,
                        input_ids=rng.integers(
                            1, 250, int(rng.integers(3, 14))).astype(np.int32),
                        max_new_tokens=int(rng.integers(3, 7)))
                for i in range(n_requests)]
        results = serve.run(reqs)

        trace_path = trace_path or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "dstpu_trace_smoke.json")
        write_chrome_trace(trace_path, metadata={"tool": "trace_smoke",
                                                 "seed": seed})
        prom = prometheus_text(tracer=get_tracer())
        timeline_ok = all(
            r.queued_s >= 0 and r.ttft_s >= 0
            and r.decode_ticks == len(r.output_ids) - 1
            and len(r.token_s) == len(r.output_ids)
            and r.token_s[0] == r.first_token_s
            and bool(np.all(np.diff(r.token_s) >= 0))
            and r.token_s[-1] <= r.finish_s for r in results)

        # ---- histogram / SLO phase (ISSUE 12): the traced run above fed
        # per-span duration histograms; check serve.tick quantiles are
        # live and monotone, exercise one SloRule to firing and back, and
        # confirm both surfaces reach the Prometheus exposition
        hist_slo_problems = _histogram_slo_phase(prom)
    finally:
        # restore the untraced default AND drop the history, so an
        # in-process caller (the tier-1 test) leaves no stale global state
        configure_tracer(enabled=False)
        get_tracer().reset()

    with open(trace_path) as f:
        doc = json.load(f)
    problems = validate_trace(doc)
    problems.extend(hist_slo_problems)
    if not timeline_ok:
        problems.append("RequestResult timeline fields inconsistent")
    if "dstpu_span_count" not in prom:
        problems.append("prometheus exposition missing span aggregates")
    site_ns = {site: measure_disabled_span_ns(site=site)
               for site in DISABLED_SITES}
    for site, ns in site_ns.items():
        if ns > 5000:   # 5µs/callsite would no longer be "noise"
            problems.append(f"disabled span cost {ns:.0f}ns at {site} "
                            "is not negligible")
    disabled_ns = site_ns.pop("overhead.probe")
    return {
        "metric": "trace-smoke",
        "trace_path": trace_path,
        "trace_events": len(doc["traceEvents"]),
        "span_names": sorted({e["name"] for e in doc["traceEvents"]
                              if e.get("ph") == "X"}),
        "requests_served": len(results),
        "disabled_span_ns": round(disabled_ns, 1),
        "disabled_site_ns": {k: round(v, 1) for k, v in site_ns.items()},
        "histogram_slo_ok": not hist_slo_problems,
        "problems": problems,
        "ok": not problems,
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None,
                    help="where to write the Chrome/Perfetto artifact "
                         "(default: $TMPDIR/dstpu_trace_smoke.json)")
    ap.add_argument("--train-steps", type=int, default=2)
    ap.add_argument("--requests", type=int, default=3)
    args = ap.parse_args(argv)
    result = run_smoke(trace_path=args.trace, train_steps=args.train_steps,
                       n_requests=args.requests)
    print(json.dumps(result))
    if not result["ok"]:
        print("trace smoke FAILED: " + "; ".join(result["problems"]),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
