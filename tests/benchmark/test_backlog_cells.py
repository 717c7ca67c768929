"""The two backlog cells end to end at a tiny size on the CPU
(``--rehearse --trace 1``), ``lib/moe_work.py``'s arithmetic on hand-worked
shapes, and the expert-layer readers on hand-built records."""
import json
import types

import pytest

from benchmark import run as bench_run
from benchmark.lib import moe_work

CAPACITY = {"slots_active_mean", "window_compiles.serve", "prefill_pad_share",
            "kv_gather_useful_share.capacity", "tick_host_ms_p50.capacity",
            "admit_host_ms_p50", "cache_misses"}


def _last(out: str, prefix: str):
    lines = [ln for ln in out.splitlines() if ln.startswith(prefix + " ")]
    return json.loads(lines[-1][len(prefix) + 1:])


@pytest.mark.parametrize("cell,expect", [
    ("olmoe-1b-7b-d12.rollout-backlog",
     CAPACITY | {"moe_experts_touched_share", "moe_live_row_share"}),
    ("opt-1.3b.chat-backlog", CAPACITY),
])
def test_rehearse_backlog_cell(cell, expect, capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", cell, "--seed", str(2 ** 31 + 26),
                             "--seconds", "1.5", "--trace", "1", "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] > 0 and res["failed"] == 0
    assert expect <= set(res["metric_names"])
    # no device metric from a CPU run, and none of the expert layer's where
    # the model has no experts
    assert not {n for n in res["metric_names"] if "roofline" in n}
    if cell.startswith("opt"):
        assert not {n for n in res["metric_names"] if n.startswith("moe_")}
    else:
        assert "layers_match_reference" in _last(out, "checks")


def _cfg():
    return types.SimpleNamespace(
        hidden_size=2048, intermediate_size=1024, num_layers=12,
        num_experts=64, num_heads=16, num_kv_heads=None, head_dim=None,
        kv_heads=16, dims_per_head=128, vocab_size=50304, max_seq_len=4096,
        tie_embeddings=False, position="rope", param_count=5_240_883_200)


def test_moe_work_arithmetic():
    cfg = _cfg()
    # one expert: 3 matrices of 2048 x 1024 in bf16
    assert moe_work.expert_bytes(cfg) == 3 * 2048 * 1024 * 2 == 12_582_912
    # everything a tick streams but the experts: params less the input
    # embedding rows, less 12 x 64 experts
    assert moe_work.non_expert_weight_bytes(cfg) == pytest.approx(
        2 * (5_240_883_200 - 50304 * 2048) - 12 * 64 * 12_582_912)
    # 16 slots x top-8 x 12 layers = 1,536 rows over 660 touched experts
    work = moe_work.expert_matmul_work(cfg, live_rows=1536, experts_touched=660)
    assert work["flops"] == 1536 * 6 * 2048 * 1024
    assert work["bytes"] == 660 * 12_582_912 + 1536 * 2 * 2048 * 2
    # a tick: the rest of the weights, 660 experts, 16 slots x 400 tokens of K/V
    assert moe_work.moe_decode_tick_bytes(cfg, 660, 6400) == pytest.approx(
        moe_work.non_expert_weight_bytes(cfg) + 660 * 12_582_912
        + 6400 * 2 * 12 * 16 * 128 * 2)


def _span(name, **attrs):
    return types.SimpleNamespace(name=name, t0=0.0, dur_s=0.01,
                                 attrs=attrs or None)


def _read(name, record):
    return bench_run._load_reader(name)(record)


def test_expert_layer_readers_on_a_hand_built_record():
    cfg = _cfg()
    decode = [_span("serve.decode", tick=i, live_rows=6400, gathered_rows=32768,
                    moe_live_rows=1536, moe_rows=1536,
                    moe_experts_touched=touched, moe_max_load=9)
              for i, touched in enumerate([660, 672, 648])]
    prefill = [_span("serve.prefill", bucket=256, tokens=200,
                     moe_live_rows=200 * 8 * 12, moe_rows=256 * 8 * 12,
                     moe_experts_touched=768, moe_max_load=70)]
    ms = 1_000_000      # ns
    trace = {
        "modules": [[0, 20 * ms, "jit_serve_decode"],
                    [30 * ms, 20 * ms, "jit_serve_decode"],
                    [60 * ms, 50 * ms, "jit_serve_prefill_256"]],
        "host": [[0, 22 * ms, "serve.decode"], [29 * ms, 22 * ms, "serve.decode"]],
        "per_op_s": {
            "jit_serve_decode@serve.decode:ragged-dot-none.1 custom-call bf16[128,1024]": 0.020,
            "jit_serve_decode@serve.tick:ragged-dot-metadata custom-call (s32[769]": 0.004,
            "jit_serve_decode@serve.decode:fusion.3 fusion bf16[16,2048]": 0.010,
            "jit_serve_prefill_256@serve.prefill:ragged-dot-none.1 custom-call bf16[2048,1024]": 0.030,
        }}
    record = {"spans": decode + prefill, "trace": trace,
              "serve": {"cfg": cfg}, "device": {"kind": "TPU v5 lite"}}
    assert _read("moe_experts_touched_share", record) == pytest.approx(
        100 * (660 + 672 + 648) / (12 * 64 * 3))
    # the padded prompt's 56 masked tokens took rows: under 100
    assert _read("moe_live_row_share", record) == pytest.approx(
        100 * (3 * 1536 + 19200) / (3 * 1536 + 24576))
    # decode programs only: 24 ms of ragged-dot ops in 40 ms of program
    assert _read("moe_expert_matmul_share", record) == pytest.approx(60.0)
    # the first two ticks' work (the trace holds two decode programs)
    work = moe_work.expert_matmul_work(cfg, 2 * 1536, 660 + 672)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert _read("moe_expert_matmul_roofline", record) == pytest.approx(
        100 * least / 0.024)
    need = moe_work.moe_decode_tick_bytes(cfg, 660, 6400)
    assert _read("moe_decode_roofline", record) == pytest.approx(
        100 * need / 819e9 / 0.020)


@pytest.mark.parametrize("name", [
    "moe_decode_roofline", "moe_expert_matmul_share",
    "moe_expert_matmul_roofline", "moe_experts_touched_share",
    "moe_live_row_share"])
def test_expert_layer_readers_read_nothing_from_a_program_without_experts(name):
    """The parent commit's spans carry no ``moe_*`` attr and its decode
    program holds no grouped matmul: None, and no raise, with and without a
    trace."""
    spans = [_span("serve.decode", tick=0, live_rows=10, gathered_rows=20),
             _span("serve.prefill", bucket=32, tokens=20)]
    trace = {"modules": [[0, 10, "jit_serve_decode"]],
             "host": [[0, 12, "serve.decode"]],
             "per_op_s": {"jit_serve_decode@serve.decode:fusion.1 fusion bf16[8]": 1e-8}}
    for tr in (None, trace):
        record = {"spans": spans, "trace": tr, "serve": {"cfg": _cfg()},
                  "device": {"kind": "TPU v5 lite"}}
        assert _read(name, record) is None
