"""trace_reduce.py: hand-worked intervals, and the recorded v5e trace."""
import json
import os

import pytest

from benchmark.lib import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(tr.__file__))


def _op(start, dur, name, opcode="fusion", result="bf16[8]", target=""):
    return [start, dur, name, opcode, result, target]


@pytest.fixture(scope="module")
def synthetic():
    """One device, times in ns.  A while [100,500) holds two leaves with a
    20 ns hole; a Mosaic kernel; an async all-gather [600,900) that compute
    covers only over [700,800); idle before, between and after."""
    ops = [
        _op(100, 400, "while.1", "while"),
        _op(100, 180, "fusion.1"),
        _op(300, 200, "fusion.2"),
        _op(520, 60, "closed_call.3", "custom-call", target="tpu_custom_call"),
        _op(700, 100, "fusion.4"),
        _op(880, 20, "all-gather-done.5", "all-gather-done"),
    ]
    async_ = [_op(600, 300, "all-gather-start.5", "all-gather-start")]
    modules = [[90, 500, "jit_step"], [595, 310, "jit_step"]]
    host = [[0, 1000, "train.batch", "python3"],
            [50, 540, "train.step", "python3"],
            [592, 400, "train.step", "python3"],
            [10, 30, "SomethingElse", "main/1"]]
    return {"devices": {"0": {"ops": ops, "modules": modules,
                              "async": async_}}, "host": host}


def test_synthetic_busy_idle_and_ops(synthetic):
    r = tr.reduce(synthetic)
    # leaves: [100,280) [300,500) [520,580) [700,800) [880,900)
    assert r["busy_s"] == pytest.approx((180 + 200 + 60 + 100 + 20) * 1e-9)
    assert r["window_s"] == pytest.approx(1000e-9)      # the train.batch span
    assert r["mosaic_s"] == pytest.approx(60e-9)
    assert r["mosaic_invocations"] == 1
    assert "jit_step@train.step:while.1 while bf16[8]" not in r["per_op_s"]
    assert r["per_op_s"]["jit_step@train.step:fusion.2 fusion bf16[8]"] == \
        pytest.approx(200e-9)


def test_synthetic_collective_exposure(synthetic):
    r = tr.reduce(synthetic)
    # collective runs over [600,900); compute (non-collective leaves) covers
    # [700,800) of it -> exposed 200 ns
    assert r["collective_s"] == pytest.approx(300e-9)
    assert r["collective_exposed_s"] == pytest.approx(200e-9)


def test_synthetic_gap_attribution(synthetic):
    r = tr.reduce(synthetic)
    gaps = r["idle_gaps_s"]
    # [0,100): middle 50 is inside train.batch and train.step starts at 50
    # [280,300) and [500,520): inside the first train.step
    # [580,700): middle 640, the second train.step; [800,880) likewise
    # [900,1000): middle 950, the second train.step still open (to 992)
    assert gaps["train.batch>train.step"] == pytest.approx(
        (100 + 20 + 20 + 120 + 80 + 100) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert tr.breakdown(r, top=2)["idle_gaps"][0][0] == "train.batch>train.step"
    assert len(tr.breakdown(r, top=2)["device_ops"]) == 2


def test_synthetic_program_time_in_span(synthetic):
    r = tr.reduce(synthetic)
    assert tr.program_ms_in_span(r, "train.step") == [
        pytest.approx(500e-6), pytest.approx(310e-6)]
    assert tr.program_ms_in_span(r, "serve.decode") == []


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 4), (6, 12)], [(0, 2), (4, 6)]),
    ([(0, 10), (20, 30)], [], [(0, 10), (20, 30)]),
    ([(0, 10)], [(0, 10)], []),
    ([(5, 8)], [(0, 6), (7, 9)], [(6, 7)]),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_union_merges_touching_and_nested():
    assert tr.union([(5, 7), (0, 3), (1, 2), (3, 4)]) == [(0, 4), (5, 7)]
    assert tr.length([(0, 4), (5, 7)]) == 6


@pytest.mark.parametrize("text,want", [
    ('%fusion.641 = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} fusion(bf16[4,2048,8192]{2,1,0} %x), kind=kOutput',
     ("fusion.641", "fusion", "bf16[4,2048,2048]", "")),
    ('%closed_call.12 = (bf16[4,16,2048,128]{3,2,1,0:T(8,128)(2,1)}, f32[4,16,1,2048]{3,2,1,0}) custom-call(bf16[4,16,2048,128]{3,2,1,0} %a), custom_call_target="tpu_custom_call", frontend_attributes={}',
     ("closed_call.12", "custom-call", "bf16[4,16,2048,128]", "tpu_custom_call")),
    ('%while.12 = (s32[]{:T(128)}, bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}) while((s32[]{:T(128)}) %t), condition=%c, body=%b',
     ("while.12", "while", "s32[]", "")),
    ('%all-gather-start.3 = (bf16[512,2048]{1,0}, bf16[2048,2048]{1,0}) all-gather-start(bf16[512,2048]{1,0} %p), dimensions={0}',
     ("all-gather-start.3", "all-gather-start", "bf16[512,2048]", "")),
])
def test_parse_hlo(text, want):
    h = tr.parse_hlo(text)
    assert (h["name"], h["opcode"], h["result"], h["target"]) == want
    assert tr.is_collective("all-gather-start") and not tr.is_collective("fusion")
    assert tr.is_collective("fusion", "async-collective-done.4")
    assert not tr.is_collective("async-start", "slice-start.8")


def test_recorded_v5e_trace():
    """The cut of a real serving trace: one whole prefill (bucket 128) and
    two decode ticks of opt-1.3b at 8 slots on one v5e."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        ex = json.load(f)
    r = tr.reduce(ex)
    ops = ex["devices"]["0"]["ops"]
    leaf = tr.leaves(ops)
    # a TPU core runs one op at a time: the leaves do not overlap, so their
    # durations sum to the union — an independent check of both
    assert len(leaf) < len(ops)
    assert r["busy_s"] == pytest.approx(sum(e[1] for e in leaf) * 1e-9,
                                        rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.289469758, rel=1e-6)
    assert r["window_s"] == pytest.approx(0.305452432, rel=1e-6)
    decode = tr.program_ms_in_span(r, "serve.decode")
    assert decode == [pytest.approx(84.97008), pytest.approx(85.000669)]
    assert tr.program_ms_in_span(r, "serve.prefill") == [
        pytest.approx(57.697871)]
    top = tr.breakdown(r)["device_ops"][0]
    assert top[0].startswith("jit_prog@serve.decode:fusion.20")
    assert sum(r["idle_gaps_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["idle_gaps_s"]["serve.admit>serve.prefill"] == pytest.approx(
        0.004506219, rel=1e-4)
