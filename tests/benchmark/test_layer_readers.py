"""The per-layer readers of ISSUE 24 on hand-built records: what each
computes from the spans, attrs and stamps the program records, and that
each returns None, without raising, where its input is absent (a program
that predates the span, a run on the CPU)."""
import json
import types

import numpy as np
import pytest

from benchmark import run as bench_run


def _read(name, record):
    return bench_run._load_reader(name)(record)


def _span(name, t0, dur_s, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, dur_s=dur_s,
                                 attrs=attrs or None)


def _result(token_s, reason="length", **kw):
    return types.SimpleNamespace(finish_reason=reason,
                                 token_s=np.asarray(token_s, np.float64), **kw)


def _serve(results):
    return {"serve": {"results": results}, "spans": []}


def test_host_bound_idle_share():
    host = [[0, 10, "serve.tick"], [2, 3, "serve.emit"], [20, 50, "serve.idle"]]
    tr = {"window_s": 10.0, "host": host, "idle_gaps_s": {
        "serve.idle": 3.0, "serve.tick": 0.1, "serve.tick>serve.emit": 0.05,
        "(no span)": 0.05}}
    # everything but the sleep, unnamed gaps included
    assert _read("host_bound_idle_share.serve",
                 {"trace": tr}) == pytest.approx(2.0)
    assert _read("host_bound_idle_share.serve", {"trace": None}) is None
    # a program without the new spans would read its whole idle share
    old = dict(tr, host=[[0, 10, "serve.tick"]])
    assert _read("host_bound_idle_share.serve", {"trace": old}) is None


def test_tick_host_ms_p50():
    def tick(n, t0, dur, decode_dur, dispatch_ms, admit=False):
        out = [_span("serve.tick", t0, dur, tick=n, slot_rids={"0": "a"}),
               _span("serve.decode", t0 + 0.001, decode_dur, tick=n,
                     dispatch_ms=dispatch_ms)]
        if admit:
            out.append(_span("serve.admit", t0 + 0.0005, 0.0001, rid="a"))
        return out

    spans = (tick(1, 0.0, 0.100, 0.090, 2.0)            # 10 + 2 = 12 ms
             + tick(2, 0.2, 0.094, 0.090, 1.0)          # 4 + 1 = 5 ms
             + tick(3, 0.4, 0.097, 0.090, 1.0)          # 7 + 1 = 8 ms
             + tick(4, 0.6, 0.300, 0.090, 1.0, admit=True))   # left out
    assert _read("tick_host_ms_p50", {"spans": spans}) == pytest.approx(8.0)
    # spans of a program that sets no dispatch_ms
    bare = [_span(s.name, s.t0, s.dur_s, tick=s.attrs["tick"])
            for s in spans if "tick" in s.attrs]
    assert _read("tick_host_ms_p50", {"spans": bare}) is None
    assert _read("tick_host_ms_p50", {}) is None


def test_itl_p99_ms(capsys):
    a = _result(np.arange(101) * 0.1)                   # 100 gaps of 100 ms
    b = _result([5.0, 5.1, 5.6])                        # 100 ms, 500 ms
    resumed = _result([np.nan, np.nan, 9.0, 9.1])       # one real gap
    failed = _result([1.0, 3.0], reason="deadline")     # not finished
    shed = _result([], reason="shed")
    got = _read("itl_p99_ms", _serve([a, b, resumed, failed, shed]))
    gaps = sorted([0.1] * 100 + [0.1, 0.5] + [0.1])
    pos = 0.99 * (len(gaps) - 1)
    want = gaps[int(pos)] + (gaps[int(pos) + 1] - gaps[int(pos)]) * (pos % 1)
    assert got == pytest.approx(want * 1e3)
    note = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("note ")]
    note = json.loads(note[-1][5:])
    assert note["itl_gaps"] == 103
    assert note["itl_p50_ms"] == pytest.approx(100.0)
    assert note["itl_max_ms"] == pytest.approx(500.0)
    # results of a program without per-token stamps
    old = types.SimpleNamespace(finish_reason="length")
    assert _read("itl_p99_ms", _serve([old])) is None
    assert _read("itl_p99_ms", _serve([_result([2.0])])) is None


def test_admit_host_ms_p50():
    def admission(rid, match, admit, prefill):
        return [_span("serve.prefix_match", 0.0, match, rid=rid),
                _span("serve.admit", 0.1, admit, rid=rid, slot=0),
                _span("serve.prefill", 0.1, prefill, rid=rid, bucket=2048)]

    spans = (admission("a", 0.002, 0.125, 0.120)        # 7 ms
             + admission("b", 0.001, 0.123, 0.120)      # 4 ms
             + admission("c", 0.004, 0.130, 0.121)      # 13 ms
             # looked up, found no room, not admitted in the window
             + [_span("serve.prefix_match", 0.9, 0.050, rid="d")])
    assert _read("admit_host_ms_p50", {"spans": spans}) == pytest.approx(7.0)
    assert _read("admit_host_ms_p50", {"spans": []}) is None


def test_kv_gather_useful_share():
    spans = [_span("serve.decode", 0.0, 0.09, tick=1, live_rows=1000,
                   gathered_rows=16384),
             _span("serve.decode", 0.1, 0.09, tick=2, live_rows=3096,
                   gathered_rows=16384)]
    assert _read("kv_gather_useful_share", {"spans": spans}) == pytest.approx(
        100.0 * 4096 / 32768)
    old = [_span("serve.decode", 0.0, 0.09, tick=1)]
    assert _read("kv_gather_useful_share", {"spans": old}) is None


def test_prefill_pad_share():
    spans = [_span("serve.prefill", 0.0, 0.12, rid="a", bucket=2048,
                   tokens=1024),
             _span("serve.prefill", 0.2, 0.03, rid="b", bucket=512,
                   tokens=512)]
    assert _read("prefill_pad_share", {"spans": spans}) == pytest.approx(40.0)
    old = [_span("serve.prefill", 0.0, 0.12, rid="a", bucket=2048)]
    assert _read("prefill_pad_share", {"spans": old}) is None


def test_step_host_ms_p50_train():
    spans = []
    for step, (batch, dev) in enumerate([(0.370, 0.366), (0.372, 0.366),
                                         (0.369, 0.3665)], start=3):
        spans += [_span("train.batch", step, batch, step=step),
                  _span("train.data", step, 0.001),
                  _span("train.step", step + 0.001, dev, step=step)]
    spans.append(_span("train.batch", 9.0, 0.5, step=6))   # its step: cut off
    assert _read("step_host_ms_p50.train",
                 {"spans": spans}) == pytest.approx(4.0)
    assert _read("step_host_ms_p50.train", {}) is None


@pytest.mark.parametrize("cell,expect", [
    ("opt-1.3b.chat", {"tick_host_ms_p50", "itl_p99_ms",
                       "kv_gather_useful_share", "prefill_pad_share"}),
    ("opt-1.3b.zero3-dp4", {"step_host_ms_p50.train"}),
])
def test_rehearsal_lists_the_new_metrics(cell, expect, capsys):
    """``run.py --rehearse --trace 1``: the readers that need no device
    trace report from a CPU run of the real program; the one that does
    (``host_bound_idle_share.serve``) is left out, not faked."""
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", cell, "--seed", "3000000019",
                             "--seconds", "1.5", "--trace", "1",
                             "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("rehearsal ")]
    names = set(json.loads(lines[-1][len("rehearsal "):])["metric_names"])
    assert expect <= names
    assert "host_bound_idle_share.serve" not in names
