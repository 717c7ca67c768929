"""Observability subsystem tests (ISSUE 4 tentpole).

Covers the span tracer (nesting, thread-local context, disabled fast path,
error capture), the bounded flight recorder (capacity, dropped accounting,
open-span dumps), both exporters (Chrome/Perfetto trace-event JSON and
Prometheus text), the bounded thread-safe ``InMemoryMonitor`` satellite,
and the tier-1 wiring of ``tools/trace_smoke.py`` (which runs a real train
step + serving stream and validates the exported trace in-process).

Dump-path integration tests (watchdog fire, ``Supervisor`` round failure,
``ServingSupervisor`` warm restart) live with their subsystems in
``test_resilience.py`` / ``test_serving_resilience.py``.
"""
import json
import os
import sys
import threading
import time

import pytest

from deepspeed_tpu.monitor import InMemoryMonitor
from deepspeed_tpu.observability import (CounterEvent, FlightRecorder,
                                         Tracer, chrome_trace_events,
                                         configure_tracer, flight_dump,
                                         get_tracer, prometheus_text,
                                         trace_span, write_chrome_trace)


@pytest.fixture
def global_trace():
    """Enable the process-global tracer on a fresh ring; restore the
    disabled default afterwards so the rest of the suite runs untraced."""
    tracer = configure_tracer(enabled=True, capacity=4096)
    tracer.reset()
    yield tracer
    configure_tracer(enabled=False)
    tracer.reset()


# ------------------------------------------------------------------ tracer

def test_disabled_tracer_is_nullop():
    t = Tracer(enabled=False)
    s1, s2 = t.span("a", x=1), t.span("b")
    assert s1 is s2                       # shared singleton, no allocation
    with s1 as sp:
        sp.set(y=2)                       # all no-ops
        sp.sync(None)
    t.count("c", 5.0)
    assert t.recorder.record_count() == 0
    assert t.aggregates() == {}


def test_span_nesting_depth_parent_duration():
    t = Tracer(enabled=True)
    with t.span("outer", step=1):
        time.sleep(0.01)
        with t.span("inner") as sp:
            sp.set(found=3)
    spans = {s.name: s for s in t.recorder.snapshot()}
    assert spans["outer"].depth == 0 and spans["outer"].parent is None
    assert spans["inner"].depth == 1 and spans["inner"].parent == "outer"
    assert spans["outer"].dur_s >= 0.01
    # children complete (and record) before their parents
    assert spans["inner"].dur_s <= spans["outer"].dur_s
    assert spans["inner"].attrs == {"found": 3}
    assert spans["outer"].attrs == {"step": 1}
    agg = t.aggregates()
    assert agg["outer"][0] == 1 and agg["inner"][0] == 1


def test_span_records_exception_type_and_still_pops():
    t = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with t.span("doomed"):
            raise ValueError("boom")
    (sp,) = t.recorder.snapshot()
    assert sp.error == "ValueError"
    assert sp.dur_s is not None
    # the stack unwound: a new span is depth 0 again
    with t.span("after"):
        pass
    assert t.recorder.snapshot()[-1].depth == 0


def test_counters_recorded():
    t = Tracer(enabled=True)
    t.count("serve.tokens", 4, tick=9)
    (ev,) = t.recorder.snapshot()
    assert isinstance(ev, CounterEvent)
    assert ev.name == "serve.tokens" and ev.value == 4.0
    assert ev.attrs == {"tick": 9}


def test_thread_local_span_stacks():
    """Two threads nest concurrently; neither sees the other's depth."""
    t = Tracer(enabled=True)
    barrier = threading.Barrier(2)
    errors = []

    def worker(tag):
        try:
            for _ in range(50):
                with t.span(f"{tag}.outer"):
                    barrier.wait(timeout=5)
                    with t.span(f"{tag}.inner"):
                        pass
        except Exception as e:   # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(f"t{i}",))
               for i in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    for sp in t.recorder.snapshot():
        if sp.name.endswith(".outer"):
            assert sp.depth == 0 and sp.parent is None
        else:
            assert sp.depth == 1
            # the parent is the SAME thread's outer, never the peer's
            assert sp.parent == sp.name.replace(".inner", ".outer")


def test_open_spans_visible_across_threads():
    t = Tracer(enabled=True)
    entered, release = threading.Event(), threading.Event()

    def worker():
        with t.span("stuck.section", tick=7):
            entered.set()
            release.wait(timeout=5)

    th = threading.Thread(target=worker, name="stuck-thread")
    th.start()
    assert entered.wait(timeout=5)
    try:
        names = [sp.name for sp in t.open_spans()]
        assert "stuck.section" in names
        dump = t.flight_dump("probe")
        assert "open spans at dump time" in dump
        assert "stuck.section" in dump and "stuck-thread" in dump
    finally:
        release.set()
        th.join()


# ---------------------------------------------------------- flight recorder

def test_flight_recorder_capacity_and_dropped():
    rec = FlightRecorder(capacity=4)
    t = Tracer(enabled=True, recorder=rec)
    for i in range(7):
        with t.span(f"s{i}"):
            pass
    assert rec.record_count() == 4
    assert rec.dropped == 3
    names = [s.name for s in rec.snapshot()]
    assert names == ["s3", "s4", "s5", "s6"]   # oldest evicted first
    assert "dropped=3" in rec.dump("why")
    rec.clear()
    assert rec.record_count() == 0 and rec.dropped == 0


def test_flight_recorder_window_filter():
    rec = FlightRecorder(capacity=16)
    t = Tracer(enabled=True, recorder=rec)
    with t.span("old"):
        pass
    time.sleep(0.15)
    with t.span("new"):
        pass
    recent = [s.name for s in rec.snapshot(last_s=0.1)]
    assert "new" in recent and "old" not in recent


def test_global_flight_dump_and_monitor_report(global_trace):
    assert flight_dump("empty") is None    # nothing recorded -> None
    with trace_span("work.unit", k=1):
        pass
    mon = InMemoryMonitor()
    text = flight_dump("after-fault", monitor=mon)
    assert text is not None and "work.unit" in text
    assert mon.reports and mon.reports[0][0] == "flight_recorder/after-fault"
    assert "work.unit" in mon.reports[0][1]


# ---------------------------------------------------------------- exporters

def test_chrome_trace_events_shape(global_trace):
    with trace_span("parent", step=2):
        with trace_span("child"):
            pass
    try:
        with trace_span("bad"):
            raise RuntimeError("x")
    except RuntimeError:
        pass
    get_tracer().count("ctr", 2.5)
    events = chrome_trace_events(get_tracer().recorder.snapshot())
    json.dumps(events)   # must be serializable
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(xs) == {"parent", "child", "bad"}
    for e in xs.values():
        assert e["dur"] >= 0 and e["ts"] > 0 and e["pid"] == os.getpid()
    # child interval inside parent interval
    p, c = xs["parent"], xs["child"]
    assert p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"]
    assert xs["bad"]["args"]["error"] == "RuntimeError"
    cs = [e for e in events if e["ph"] == "C"]
    assert cs and cs[0]["args"]["value"] == 2.5
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)


def test_write_chrome_trace_file(global_trace, tmp_path):
    with trace_span("unit.a"):
        pass
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, metadata={"run": "test"})
    doc = json.load(open(path))
    assert doc["otherData"] == {"run": "test"}
    assert any(e["name"] == "unit.a" for e in doc["traceEvents"])
    assert not os.path.exists(path + ".tmp")   # atomic publish


def test_prometheus_text_gauges_and_spans(global_trace):
    mon = InMemoryMonitor(max_events=8)
    mon.write_events([("serve/queue_depth", 3.0, 1),
                      ("serve/queue_depth", 5.0, 2),
                      ("Train/Samples/train_loss", 0.25, 2)])
    with trace_span("serve.tick"):
        pass
    text = prometheus_text(monitor=mon, tracer=get_tracer())
    assert "dstpu_serve_queue_depth 5" in text           # latest value wins
    assert "dstpu_Train_Samples_train_loss 0.25" in text  # sanitized name
    assert 'dstpu_span_count{span="serve.tick"} 1' in text
    assert 'dstpu_span_seconds_total{span="serve.tick"}' in text
    assert "dstpu_monitor_dropped_events_total 0" in text
    assert "dstpu_flight_recorder_dropped_total 0" in text


# -------------------------------------------- InMemoryMonitor (satellite)

def test_inmemory_monitor_bounded_with_dropped_counter():
    mon = InMemoryMonitor(max_events=5)
    mon.write_events([("g", float(i), i) for i in range(8)])
    assert len(mon.events) == 5
    assert mon.dropped_events == 3
    # series/latest semantics hold over the retained window
    assert mon.series("g") == [(i, float(i)) for i in range(3, 8)]
    assert mon.latest("g") == 7.0
    assert mon.latest("missing") is None
    with pytest.raises(ValueError):
        InMemoryMonitor(max_events=0)


def test_inmemory_monitor_concurrent_writers_and_readers():
    """Watchdog/supervisor threads emit while the loop reads — no
    corruption, no mutation-during-iteration, exact drop accounting."""
    mon = InMemoryMonitor(max_events=64)
    n_threads, per_thread = 4, 200
    errors = []

    def writer(tag):
        try:
            for i in range(per_thread):
                mon.write_events([(f"w{tag}", float(i), i)])
        except Exception as e:   # pragma: no cover
            errors.append(e)

    def reader():
        try:
            for _ in range(200):
                mon.series("w0")
                mon.latest("w1")
        except Exception as e:   # pragma: no cover
            errors.append(e)

    threads = ([threading.Thread(target=writer, args=(t,))
                for t in range(n_threads)]
               + [threading.Thread(target=reader) for _ in range(2)])
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert len(mon.events) == 64
    assert mon.dropped_events == n_threads * per_thread - 64


# --------------------------------------------------- trace smoke (tier-1)

def test_trace_smoke_tool(tmp_path):
    """Satellite: tools/trace_smoke.py runs a real train step + serving
    stream in-process, validates the exported Chrome trace (names present,
    non-negative nesting) and measures the disabled-tracer overhead."""
    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, os.pardir, "tools")
    sys.path.insert(0, tools)
    try:
        from trace_smoke import run_smoke
    finally:
        sys.path.remove(tools)
    out = run_smoke(trace_path=str(tmp_path / "smoke_trace.json"),
                    train_steps=1, n_requests=3)
    assert out["ok"], out["problems"]
    assert set(out["span_names"]) >= {"train.batch", "train.step",
                                      "serve.tick", "serve.admit",
                                      "serve.prefill", "serve.decode"}
    # the overhead guarantee docs/OBSERVABILITY.md quotes: a disabled
    # instrumentation site costs well under a microsecond
    assert out["disabled_span_ns"] < 5000
    # and the two per-program sites of the serving loop (ISSUE 34)
    assert set(out["disabled_site_ns"]) == {"serve.launch", "serve.fetch"}
    assert max(out["disabled_site_ns"].values()) < 5000
    assert {"serve.launch", "serve.fetch"} <= set(out["span_names"])
    # the global tracer was restored to disabled
    assert not get_tracer().enabled


# ------------------------------------------------- /metrics endpoint (ISSUE 5)
def test_metrics_endpoint_serves_prometheus_text():
    """The stdlib /metrics server renders the live monitor + tracer state
    per scrape; non-metrics paths 404 (observability/export.py)."""
    import urllib.error
    import urllib.request

    from deepspeed_tpu.observability import start_metrics_server

    mon = InMemoryMonitor()
    mon.write_events([("pod/generation", 3.0, 1),
                      ("serve/queue_depth", 2.0, 1)])
    srv = start_metrics_server(port=0, monitor=mon)
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "dstpu_pod_generation 3" in body
        assert "dstpu_serve_queue_depth 2" in body
        # live view: a later event is visible on the next scrape
        mon.write_events([("pod/generation", 4.0, 2)])
        with urllib.request.urlopen(url) as r:
            assert "dstpu_pod_generation 4" in r.read().decode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/nope")
        assert ei.value.code == 404
    finally:
        srv.close()


def test_maybe_start_metrics_server_is_env_gated(monkeypatch):
    import urllib.request

    from deepspeed_tpu.observability import maybe_start_metrics_server
    from deepspeed_tpu.observability import export as export_mod

    monkeypatch.delenv("DS_TPU_METRICS_PORT", raising=False)
    assert maybe_start_metrics_server() is None
    monkeypatch.setenv("DS_TPU_METRICS_PORT", "not-a-port")
    assert maybe_start_metrics_server() is None
    monkeypatch.setenv("DS_TPU_METRICS_PORT", "0")
    monkeypatch.setattr(export_mod, "_METRICS_SERVER", None)
    srv = maybe_start_metrics_server()
    try:
        assert srv is not None
        # second call returns the running server and attaches the monitor
        mon = InMemoryMonitor()
        mon.write_events([("pod/live_hosts", 4.0, 1)])
        assert maybe_start_metrics_server(mon) is srv
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics").read().decode()
        assert "dstpu_pod_live_hosts 4" in body
    finally:
        srv.close()
        monkeypatch.setattr(export_mod, "_METRICS_SERVER", None)


def test_metrics_port_collision_falls_back_to_ephemeral(monkeypatch):
    """ISSUE 7 satellite: with N engines sharing a host, the second
    process finding DS_TPU_METRICS_PORT already bound must neither crash
    at init nor silently lose its endpoint — it binds an ephemeral port
    and reports the ACTUAL port (get_metrics_server / health())."""
    from deepspeed_tpu.observability import (MetricsServer,
                                             get_metrics_server,
                                             maybe_start_metrics_server)
    from deepspeed_tpu.observability import export as export_mod

    first = MetricsServer(port=0, monitor=None)   # "the first process"
    try:
        monkeypatch.setenv("DS_TPU_METRICS_PORT", str(first.port))
        monkeypatch.setattr(export_mod, "_METRICS_SERVER", None)
        srv = maybe_start_metrics_server()        # "the second process"
        try:
            assert srv is not None
            assert srv.port != first.port and srv.port > 0
            assert get_metrics_server() is srv
        finally:
            if srv is not None:
                srv.close()
    finally:
        first.close()
        monkeypatch.setattr(export_mod, "_METRICS_SERVER", None)


def test_serving_engine_health_reports_bound_metrics_port(monkeypatch):
    """The serving engine wires the env-gated endpoint at init and
    health() exposes the bound port (the fleet advertisement reads the
    same field) — None when the endpoint is not enabled."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.observability import export as export_mod

    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)
    monkeypatch.delenv("DS_TPU_METRICS_PORT", raising=False)
    serve = engine.serving(b_slots=1, page_size=8, max_model_len=32)
    assert serve.health()["metrics_port"] is None
    monkeypatch.setenv("DS_TPU_METRICS_PORT", "0")
    monkeypatch.setattr(export_mod, "_METRICS_SERVER", None)
    try:
        serve2 = engine.serving(b_slots=1, page_size=8, max_model_len=32)
        port = serve2.health()["metrics_port"]
        assert isinstance(port, int) and port > 0
    finally:
        srv = export_mod._METRICS_SERVER
        if srv is not None:
            srv.close()
        monkeypatch.setattr(export_mod, "_METRICS_SERVER", None)


# --------------------------------------- KV-page tiering gauges (ISSUE 11)

@pytest.mark.slow
def test_health_and_prometheus_carry_tier_gauges():
    """ISSUE 11 satellite: health() and the Prometheus exposition grow the
    tiering quartet — demoted_pages / host_tier_bytes / promotions_total /
    demotions_total (serve/tier_* gauge names) — sourced from a real
    demote/promote cycle under pool pressure."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.models import CausalLM

    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(3))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)
    mon = InMemoryMonitor()
    serve = engine.serving(b_slots=1, page_size=8, max_model_len=40,
                           num_pages=8, host_tier_pages=16, monitor=mon)
    rng = np.random.default_rng(5)
    systems = [rng.integers(1, 250, 17).astype(np.int32) for _ in range(3)]
    serve.run([Request(rid=i,
                       input_ids=np.concatenate(
                           [systems[i % 3],
                            rng.integers(1, 250, 3).astype(np.int32)]),
                       max_new_tokens=4)
               for i in range(9)])
    h = serve.health()
    assert serve.demotions > 0 and serve.promotions > 0
    assert h["demotions_total"] == serve.demotions
    assert h["promotions_total"] == serve.promotions
    assert h["demoted_pages"] == serve._prefix.demoted
    assert h["host_tier_bytes"] == serve._tier.bytes()
    assert h["host_tier_capacity_pages"] == 16
    assert h["demoted_pages_hwm"] >= h["demoted_pages"]
    # gauge series landed on the monitor...
    for gauge in ("serve/tier_demoted_pages", "serve/tier_host_bytes",
                  "serve/tier_demotions_total",
                  "serve/tier_promotions_total"):
        assert mon.series(gauge), f"missing gauge {gauge}"
    assert mon.latest("serve/tier_demotions_total") == float(serve.demotions)
    # ...and reach the Prometheus exposition like every other gauge
    text = prometheus_text(monitor=mon)
    assert "dstpu_serve_tier_demoted_pages" in text
    assert "dstpu_serve_tier_host_bytes" in text
    assert f"dstpu_serve_tier_promotions_total {serve.promotions}" in text
    assert f"dstpu_serve_tier_demotions_total {serve.demotions}" in text
    # an untiered engine carries the keys at zero (dashboards need not
    # branch on configuration)
    plain = engine.serving(b_slots=1, page_size=8, max_model_len=40)
    hp = plain.health()
    assert hp["demoted_pages"] == 0 and hp["host_tier_bytes"] == 0
    assert hp["demotions_total"] == 0 and hp["promotions_total"] == 0
