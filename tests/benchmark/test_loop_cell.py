"""The cell of ISSUE 44 (``ouro-2.6b.mathrollout-backlog``): the
configuration's file against the catalog's numbers and its own arithmetic,
the traffic file, the manifest's rules with the tenth cell, ``lib/
loop_work.py``'s arithmetic, the three readers of a looped model on
hand-built records (and on a program that has no such counters), and the
cell end to end at a tiny size on the CPU (``--rehearse --trace 1``)."""
import json
import os
import types

import pytest

from benchmark import run as bench_run
from tests.benchmark import test_manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, CELL = "ouro-2.6b", "ouro-2.6b.mathrollout-backlog"
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
NEW = {"loop_decode_roofline": ("paged forward", "device_trace", "higher"),
       "loop_kv_traffic_share": ("paged forward", "program_counter",
                                 "higher"),
       "page_wait_tick_share": ("serving scheduler", "program_span",
                                "lower")}
# what every serving backlog cell reports
SHARED = {"slots_active_mean", "prefill_ms_p50", "window_compiles.serve",
          "device_idle_share.serve", "peak_hbm_gb.serve",
          "host_bound_idle_share.serve", "admit_host_ms_p50",
          "prefill_pad_share", "decode_ms_p50.capacity",
          "tick_host_ms_p50.capacity", "kv_gather_useful_share.capacity",
          "gap_fetch_share", "gap_host_share", "gap_launch_share",
          "host_busy_share"}


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


def _body():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "mathrollout-backlog.json")) as f:
        return json.load(f)


def _ouro_cfg():
    from deepspeed_tpu.models import get_config

    return get_config("ouro-2.6b")


# ---------------------------------------------------------- the data files

def test_the_configuration_is_the_catalogs_with_nothing_reduced(manifest):
    body = _body()
    published = {      # the catalog's ``config``, every number of it
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    for key, value in published.items():
        assert body[key] == value, key
    assert body["reduced"] == [] and "published" not in body
    assert (body["name"], body["source"]) == (CONFIG, SOURCE)
    assert body["reference"] == "benchmark.lib.reference_ouro"
    assert body["transformer_config"] == {"base": "ouro-2.6b",
                                          "overrides": {}}
    assert len(body["assumed"]) >= 8
    for said in ("input_layernorm_2", "post_attention_layernorm_2",
                 "after EVERY pass", "r x 48 + l", "NOT built",
                 "half-split", "bfloat16", "no pass reuses"):
        assert any(said in a for a in body["assumed"]), said
    for said in ("51,388,416", "2,667,972,608", "5.34 GB", "1,572,864 B",
                 "201.3 MB", "40 pages", "8.05 GB", "13.4 GB of 16",
                 "nothing reduced"):
        assert said in body["deployment"], said
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert (entry["reduced"], entry["source"], entry["file"]) == (
        [], SOURCE, f"benchmark/configs/{CONFIG}.json")

    from benchmark.lib import system
    from deepspeed_tpu.models.transformer import cache_depth

    cfg = system.transformer_config(body, rehearse=False)
    assert cfg == _ouro_cfg()
    assert (cfg.num_layers, cfg.kv_heads, cfg.dims_per_head, cfg.rope_theta,
            cfg.norm_eps, cfg.loop_passes, cfg.sandwich_norm) == (
        48, body["num_key_value_heads"], body["head_dim"],
        body["rope_theta"], body["rms_norm_eps"], body["total_ut_steps"],
        True)
    # the file's arithmetic
    assert cfg.param_count == 2_667_972_608
    assert round(cfg.param_count * 2 / 1e9, 2) == 5.34
    per_token = cache_depth(cfg) * 2 * 16 * 128 * 2
    assert per_token == 1_572_864
    assert round(per_token * 128 / 1e6, 1) == 201.3
    assert round(40 * per_token * 128 / 1e9, 2) == 8.05
    assert round((cfg.param_count * 2 + 40 * per_token * 128) / 1e9, 1) \
        == 13.4
    small = system.transformer_config(body, rehearse=True)
    assert (small.loop_passes, small.sandwich_norm) == (4, True)
    assert 2 <= small.num_layers <= 4 and small.hidden_size == 64


def test_the_traffic_is_what_issue_44_names():
    traffic = _traffic()
    assert traffic["kind"] == "serve-backlog"
    assert traffic["size_seed"] == 20261044
    geo = traffic["engine"]
    assert {k: geo[k] for k in ("b_slots", "page_size", "max_model_len")} \
        == {"b_slots": 16, "page_size": 128, "max_model_len": 1024}
    # far under the full reservation: pages, not slots, bound admission
    assert 36 <= geo["num_pages"] <= 44 < 1 + 16 * (1024 // 128) == 129
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 96, "sigma": 0.6, "min": 32,
        "max": 256}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64,
        "max": 640}
    assert traffic["parity"] == {"prompt": 600, "decode": 32}
    assert traffic["parity"]["prompt"] % 128
    assert traffic["drain_seconds"] == 60 and traffic["trace_ticks"] == 60
    assert "sized on the chip" in traffic["notes"]
    small = traffic["rehearse"]["engine"]
    full = 1 + small["b_slots"] * (small["max_model_len"]
                                   // small["page_size"])
    assert small["num_pages"] < full
    # the longest request fits a slot, and the pool
    assert 256 + 640 <= 1024 and -(-(256 + 640) // 128) < geo["num_pages"]


def test_the_cell_reports_what_issue_44_lists(manifest):
    reported = {m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", [])}
    assert reported == set(NEW) | SHARED
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (layer, source, better) in NEW.items():
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == (layer, source, "serve_tokens_per_s", "%",
                                 better)
    # the stack is streamed once a PASS: the dense tick's roofline would
    # count the weights once and read a quarter of the truth
    assert CELL not in by_name["decode_roofline.capacity"]["workloads"]
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "mathrollout-backlog")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    # what was added is last in its list
    assert manifest["configs"][-1]["name"] == CONFIG
    assert manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-3:]] == list(NEW)


@pytest.mark.parametrize("rule", [
    test_manifest.test_keys_names_units,
    test_manifest.test_moves_and_coverage,
    test_manifest.test_files_exist,
    test_manifest.test_config_files_agree_with_what_is_run])
def test_the_manifest_rules_hold_with_the_tenth_cell(manifest, rule):
    assert len(manifest["workloads"]) == 10 and len(manifest["configs"]) == 7
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1
    rule(manifest)


# ------------------------------------------------ loop_work and the readers

def test_loop_work_arithmetic():
    from benchmark.lib import loop_work

    cfg = _ouro_cfg()
    assert loop_work.is_looped(cfg)
    assert loop_work.layer_params(cfg) == 51_388_416
    stack, head = 48 * 51_388_416 + 2048, 49152 * 2048
    assert (loop_work.stack_params(cfg), loop_work.head_params(cfg)) == (
        stack, head)
    # every parameter once: what is left is the embedding a token looks up
    assert cfg.param_count == stack + 2 * head
    assert round(2 * stack / 1e9, 2) == 4.93
    assert loop_work.kv_token_bytes(cfg) == 1_572_864
    assert loop_work.attention_ops_per_row(cfg) == 8192
    kv = 3_000 * 1_572_864
    work = loop_work.decode_tick_work(cfg, kv, 3_000, 12)
    assert work["kv_bytes"] == kv
    assert work["weight_bytes"] == 2 * (4 * stack + head)
    assert round(work["weight_bytes"] / 1e9, 1) == 19.9
    # ISSUE 44's tick: ~24.6 GB, 30 ms at 819 GB/s
    total = work["weight_bytes"] + work["kv_bytes"]
    assert round(total / 1e9, 1) == 24.7 and 29 < total / 819e9 * 1e3 < 31
    assert work["flops"] == pytest.approx(
        2.0 * 12 * (4 * stack + head) + 3_000 * 4 * 48 * 8192)
    assert work["flops"] / 197e12 < 0.05 * total / 819e9   # bytes bound it
    assert loop_work.tick_work(cfg, dict(
        kv_bytes=kv, live_rows=3_000, own_slots=12)) == work


def _loop_record():
    def decode(t0, rows, slots):
        return _span("serve.decode", t0, 0.045, live_rows=rows,
                     gathered_rows=rows + 700, own_slots=slots, passes=4,
                     kv_bytes=(rows + slots) * 1_572_864)

    def tick(t0, wait, free):
        return _span("serve.tick", t0, 0.046, tick=1, page_wait=wait,
                     pages_free=free, slot_rids={})

    spans = [tick(1.0, 1, 2), decode(1.0, 2_900, 12),
             tick(2.0, 1, 1), decode(2.0, 3_100, 12),
             tick(3.0, 0, 5), decode(3.0, 3_000, 13),
             tick(3.5, 1, 0),
             # the drain: past the window's end, left out
             tick(50.0, 0, 30), decode(50.0, 300, 2)]
    # two launches fall into the first span and none into the second: the
    # roofline reads the programs themselves, one a tick
    trace = {"host": [[1.0e9, 90e6, "serve.decode"],
                      [2.0e9, 45e6, "serve.decode"],
                      [3.0e9, 45e6, "serve.decode"]],
             "modules": [[1.0e9 + 1, 41.0e6, "jit_serve_decode"],
                         [1.045e9, 43.0e6, "jit_serve_decode"],
                         [1.5e9, 30.0e6, "jit_serve_prefill_128"],
                         [3.0e9 + 1, 42.0e6, "jit_serve_decode"]]}
    return {"spans": spans, "trace": trace, "device": {"kind": "TPU v5 lite"},
            "serve": {"t_end": 41.0, "cfg": _ouro_cfg()}}


def test_loop_readers_on_a_hand_built_record():
    from benchmark.lib import loop_work

    rec = _loop_record()
    cfg = rec["serve"]["cfg"]
    ticks = [(2_900, 12), (3_100, 12), (3_000, 13)]
    w = [loop_work.decode_tick_work(cfg, (r + s) * 1_572_864, r, s)
         for r, s in ticks]
    kv = sum(x["kv_bytes"] for x in w)
    share = _read("loop_kv_traffic_share", rec)
    assert share == pytest.approx(
        100 * kv / (kv + sum(x["weight_bytes"] for x in w)))
    assert 15 < share < 25
    mean = loop_work.decode_tick_work(
        cfg, sum((r + s) for r, s in ticks) / 3 * 1_572_864, 3_000, 37 / 3)
    need = max((mean["kv_bytes"] + mean["weight_bytes"]) / 819e9,
               mean["flops"] / 197e12)
    got = _read("loop_decode_roofline", rec)
    assert got == pytest.approx(100 * need / 42.0e-3)
    assert 60 < got < 100
    # the dense reader's work counts the stack ONCE: a quarter of the truth
    from benchmark.lib import flops

    assert flops.decode_tick_bytes(cfg, 3_000) < 0.45 * (
        mean["kv_bytes"] + mean["weight_bytes"])
    assert _read("page_wait_tick_share", rec) == pytest.approx(75.0)
    assert 0 < _read("kv_gather_useful_share.capacity", rec) < 100


@pytest.mark.parametrize("name", sorted(NEW))
def test_loop_readers_read_nothing_from_a_program_without_the_counters(name):
    """The parent's program has no ``loop_passes`` and its spans no
    ``kv_bytes`` or ``page_wait``: each reader returns None and does not
    raise, with a trace or without."""
    from deepspeed_tpu.models import get_config

    rec = _loop_record()
    old = [_span("serve.tick", 1.0, 0.01, tick=1, slot_rids={}),
           _span("serve.decode", 1.0, 0.01, tick=1, live_rows=9,
                 gathered_rows=256, own_slots=3),
           _span("serve.prefill", 1.2, 0.02, tokens=40, bucket=64)]
    assert _read(name, dict(rec, spans=old)) is None
    assert _read(name, dict(rec, trace=None, spans=old)) is None
    assert _read(name, {"trace": None}) is None
    if name == "page_wait_tick_share":      # any engine's ticks carry it
        return
    other = dict(rec, serve={"t_end": 41.0,
                             "cfg": get_config("olmoe-1b-7b", num_layers=12)})
    assert _read(name, other) is None
    bare = types.SimpleNamespace(num_layers=48, hidden_size=2048)
    assert _read(name, dict(rec, serve={"t_end": 41.0, "cfg": bare})) is None


def test_rehearse_the_cell(capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 44),
                             "--seconds", "1.5", "--trace", "1", "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] == 10 and res["failed"] == 0
    assert {"loop_kv_traffic_share", "page_wait_tick_share",
            "kv_gather_useful_share.capacity", "slots_active_mean",
            "window_compiles.serve", "prefill_pad_share",
            "tick_host_ms_p50.capacity", "admit_host_ms_p50",
            "host_busy_share"} <= set(res["metric_names"])
    checks = _last(out, "checks")
    assert checks["layers_match_reference"] and checks["pages_balanced"]
    assert checks["inventory_unchanged"] and checks["no_request_failed"]
    notes = [json.loads(ln[5:]) for ln in out.splitlines()
             if ln.startswith("note ")]
    layers = next(n["layer_checks"] for n in notes if "layer_checks" in n)
    assert set(layers) == {"block_padded_prompt", "pass_1_x", "pass_2_x",
                           "pass_3_x", "pass_4_x"}
    assert all(c["rel_err"] <= c["tol"] for c in layers.values())
