"""The cell of ISSUE 30 (``mimo-v2.5-ep16-d7.reasoning-backlog``): the
configuration's file against what it says it cut, ``lib/hybrid_work.py``'s
arithmetic, the five readers of a model with two kinds of layer on
hand-built records, and the cell end to end at a tiny size on the CPU
(``--rehearse --trace 1``)."""
import json
import os
import types

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "mimo-v2.5-ep16-d7.reasoning-backlog"
HYBRID = {"kv_window_rows_share", "kv_full_read_useful_share",
          "moe_local_pair_share", "moe_held_touched_share"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _read(name, record):
    return bench_run._load_reader(name)(record)


def _span(name, t0, dur_s, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, dur_s=dur_s,
                                 attrs=attrs or None)


def _last(out: str, prefix: str):
    lines = [ln for ln in out.splitlines() if ln.startswith(prefix + " ")]
    return json.loads(lines[-1][len(prefix) + 1:])


def test_rehearse_the_cell(capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 26),
                             "--seconds", "1.5", "--trace", "1", "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] > 0 and res["failed"] == 0
    assert HYBRID | {"slots_active_mean", "window_compiles.serve",
                     "prefill_pad_share", "tick_host_ms_p50.capacity",
                     "admit_host_ms_p50", "cache_misses"} <= set(
        res["metric_names"])
    # no device metric from a CPU run, and not the one-pool reader
    assert not {n for n in res["metric_names"] if "roofline" in n}
    assert "kv_gather_useful_share.capacity" not in res["metric_names"]
    assert "layers_match_reference" in _last(out, "checks")


def test_the_hybrid_metrics_list_the_new_cell_alone(manifest):
    """No cell of a one-pool model is asked for them, as a rule read from
    the configurations and not a list of one: the two held-share readers
    are reported by exactly the serving cells whose configuration holds a
    share of its experts (``n_routed_experts`` under ``reduced``), the two
    K/V readers and the roofline by this cell and by none whose model has
    one kind of attention layer."""
    from benchmark.lib import system
    from deepspeed_tpu.models.transformer import is_hybrid

    configs = {c["name"]: c for c in manifest["configs"]}
    config_of = {c["name"]: configs[c["config"]]
                 for c in manifest["workloads"]}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    (serving,) = [set(m["workloads"]) for m in manifest["end_to_end"]
                  if m["name"] == "serve_tokens_per_s"]
    held = {cell for cell in serving
            if "n_routed_experts" in config_of[cell]["reduced"]}
    for name in ("moe_local_pair_share", "moe_held_touched_share"):
        assert set(by_name[name]["workloads"]) == held, name
    for name in ("kv_window_rows_share", "kv_full_read_useful_share",
                 "hybrid_decode_roofline"):
        assert CELL in by_name[name]["workloads"]
        for cell in by_name[name]["workloads"]:
            with open(os.path.join(ROOT, config_of[cell]["file"])) as f:
                cfg = system.transformer_config(json.load(f), rehearse=False)
            assert is_hybrid(cfg), (name, cell)
    for name in HYBRID | {"hybrid_decode_roofline"}:
        assert by_name[name]["moves"] == "serve_tokens_per_s"


def test_a_cut_configuration_states_what_it_cut_and_names_its_reference(manifest):
    """A configuration that holds a share of a model (ISSUE 30) says so in
    its file: ``published`` beside every key of ``reduced``, the deployment
    the share stands for, what was assumed, and the module that is its
    plain reference, which offers what the serving kinds call."""
    import importlib

    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["assumed"] and body["deployment"]
        if "published" in body:
            assert set(body["published"]) == set(body["reduced"])
            for key, was in body["published"].items():
                assert body[key] != was and body[key] < was, key
        ref = importlib.import_module(
            body.get("reference", "benchmark.lib.reference"))
        assert callable(ref.reference_logits) and callable(ref.rel_err)
    body = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "mimo-v2.5-ep16-d7.json")))
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 48,
                                 "n_routed_experts": 256,
                                 "vocab_size": 152576}
    # no width differs from the published config, and the lists stay whole
    assert (body["hidden_size"], body["head_dim"], body["v_head_dim"],
            body["intermediate_size"], body["moe_intermediate_size"],
            body["num_experts_per_tok"], body["sliding_window"]) == (
        4096, 192, 128, 16384, 2048, 8, 128)
    assert len(body["hybrid_layer_pattern"]) == 48 == len(body["moe_layer_freq"])
    from benchmark.lib import system

    cfg = system.transformer_config(body, rehearse=False)
    assert cfg.layer_pattern[:7] == tuple(
        "window" if k else "full" for k in body["hybrid_layer_pattern"][:7])
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.moe_top_k,
            cfg.dense_layers) == (256, 16, 8, 1)
    assert (cfg.kv_heads, cfg.window_kv_heads, cfg.rope_theta,
            cfg.window_rope_theta, cfg.rotary_dim) == (
        body["num_key_value_heads"], body["swa_num_key_value_heads"],
        body["rope_theta"], body["swa_rope_theta"],
        int(body["head_dim"] * body["partial_rotary_factor"]))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "reasoning-backlog.json")))
    assert traffic["parity"]["prompt"] > 20 * body["sliding_window"]
    assert traffic["engine"] == {"b_slots": 32, "page_size": 128,
                                 "max_model_len": 16384}


def _mimo_cfg():
    from deepspeed_tpu.models import get_config

    return get_config("mimo-v2.5", num_layers=7, moe_experts_held=16,
                      vocab_size=19072)


def test_hybrid_work_arithmetic():
    from benchmark.lib import hybrid_work

    cfg = _mimo_cfg()
    assert hybrid_work.head_row_bytes(cfg) == (192 + 128) * 2
    assert hybrid_work.expert_bytes(cfg) == 3 * 4096 * 2048 * 2
    window = 4096 * 64 * 192 + 4096 * 8 * 320 + 64 * 128 * 4096
    full = 4096 * 64 * 192 + 4096 * 4 * 320 + 64 * 128 * 4096
    want = (5 * (window + 64) + 2 * full + 7 * 2 * 4096      # attention, norms
            + 3 * 4096 * 16384                               # layer 0's MLP
            + 6 * (4096 * 256 + 256)                         # routers, biases
            + 19072 * 4096 + 4096)                           # head, final norm
    assert hybrid_work.non_expert_weight_bytes(cfg) == 2.0 * want
    assert hybrid_work.decode_tick_bytes(cfg, 60, 1_000_000) == pytest.approx(
        2.0 * want + 60 * 50_331_648 + 1_000_000 * 640)
    # every parameter is counted once: what is left are the held experts
    # and the embedding rows a tick looks up
    assert cfg.param_count * 2 == pytest.approx(
        2.0 * want + 6 * 16 * 50_331_648 + 19072 * 4096 * 2)


def _hybrid_record():
    def decode(t0, **kw):
        return _span("serve.decode", t0, 0.015, **kw)

    spans = [
        decode(1.0, kv_rows_full=8 * 40960, kv_live_rows_full=8 * 30000,
               kv_rows_window=40 * 8192, kv_live_rows_window=40 * 4096,
               moe_rows=14, moe_pairs=1536, moe_local_pairs=90,
               moe_experts_touched=60, moe_experts_held=96),
        decode(2.0, kv_rows_full=8 * 40960, kv_live_rows_full=8 * 34000,
               kv_rows_window=40 * 8192, kv_live_rows_window=40 * 4096,
               moe_rows=14, moe_pairs=1536, moe_local_pairs=102,
               moe_experts_touched=66, moe_experts_held=96),
        # the drain: past the window's end, left out
        decode(50.0, kv_rows_full=8, kv_live_rows_full=8, kv_rows_window=8,
               kv_live_rows_window=8, moe_rows=1, moe_pairs=8,
               moe_local_pairs=8, moe_experts_touched=1, moe_experts_held=96),
        _span("serve.prefill", 1.5, 0.3, moe_rows=1, moe_pairs=6 * 8 * 4000,
              moe_local_pairs=11808, moe_experts_touched=96,
              moe_experts_held=96, kv_rows_full=1, kv_live_rows_full=1,
              kv_rows_window=1, kv_live_rows_window=1),
    ]
    trace = {"host": [[1.0e9, 15e6, "serve.decode"], [2.0e9, 15e6, "serve.decode"]],
             "modules": [[1.0e9 + 1, 12.0e6, "jit_serve_decode"],
                         [2.0e9 + 1, 14.0e6, "jit_serve_decode"]]}
    return {"spans": spans, "trace": trace, "device": {"kind": "TPU v5 lite"},
            "serve": {"t_end": 41.0, "cfg": _mimo_cfg()}}


def test_hybrid_readers_on_a_hand_built_record():
    from benchmark.lib import hybrid_work

    rec = _hybrid_record()
    assert _read("kv_window_rows_share", rec) == pytest.approx(
        100 * 40 * 8192 / (40 * 8192 + 8 * 40960))
    assert _read("kv_full_read_useful_share", rec) == pytest.approx(
        100 * 64000 / 81920)
    assert _read("moe_local_pair_share", rec) == pytest.approx(
        100 * (90 + 102 + 11808) / (1536 * 2 + 192000))
    assert _read("moe_held_touched_share", rec) == pytest.approx(
        100 * 126 / 192)
    need = hybrid_work.decode_tick_bytes(
        rec["serve"]["cfg"], 63, 8 * 32000 + 40 * 4096)
    assert _read("hybrid_decode_roofline", rec) == pytest.approx(
        100 * need / 819e9 / 13.0e-3)


@pytest.mark.parametrize("name", [
    "kv_window_rows_share", "kv_full_read_useful_share",
    "moe_local_pair_share", "moe_held_touched_share",
    "hybrid_decode_roofline"])
def test_hybrid_readers_read_nothing_from_a_program_without_the_attrs(name):
    """The parent's spans (one pool, every expert held) carry none of the new
    attrs: each reader returns None and does not raise, with a trace or
    without."""
    old = [_span("serve.decode", 1.0, 0.01, tick=1, live_rows=100,
                 gathered_rows=256, moe_rows=12, moe_live_rows=12,
                 moe_experts_touched=9, moe_max_load=2),
           _span("serve.prefill", 1.2, 0.02, tokens=40, bucket=64)]
    rec = dict(_hybrid_record(), spans=old)
    assert _read(name, rec) is None
    assert _read(name, dict(rec, trace=None)) is None
    assert _read(name, {"trace": None}) is None
