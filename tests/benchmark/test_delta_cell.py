"""The cell of ISSUE 51 (``olmo-hybrid-7b-d16.thinkrollout-backlog``): the
configuration's file against the catalog row and what it says it cut,
``lib/delta_work.py``'s arithmetic by hand, the four readers on hand-built
records, the manifest's rules with the twelfth cell, what
``test_window_account.py``'s one pin still holds beside its last line
(``pinned_tail.py``), and the cell end to end at a tiny size on the CPU
(``--rehearse --trace 1``)."""
import json
import os
import types

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "olmo-hybrid-7b-d16"
CELL = CONFIG + ".thinkrollout-backlog"
NEW = ["delta_decode_roofline", "delta_prefill_roofline",
       "delta_step_roofline", "delta_state_traffic_share"]
OLDER = {"slots_active_mean", "window_compiles.serve",
         "device_idle_share.serve", "peak_hbm_gb.serve",
         "host_bound_idle_share.serve", "prefill_pad_share", "prefill_ms_p50",
         "admit_host_ms_p50", "decode_ms_p50.capacity",
         "tick_host_ms_p50.capacity", "gap_fetch_share", "gap_host_share",
         "gap_launch_share", "host_busy_share"}
PR49 = {"traced_serve_tokens_per_s", "trace_stop_block_s",
        "window_tokens_per_tick", "window_decode_time_share",
        "window_prefill_time_share", "window_tick_ms_mean"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _body():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _cfg():
    from benchmark.lib import system

    return system.transformer_config(_body(), rehearse=False)


def _read(name, record):
    return bench_run._load_reader(name)(record)


def _span(name, t0, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, dur_s=0.01,
                                 attrs=attrs or None)


def _last(out: str, prefix: str):
    lines = [ln for ln in out.splitlines() if ln.startswith(prefix + " ")]
    return json.loads(lines[-1][len(prefix) + 1:])


# ---------------------------------------------------------- the data files

PUBLISHED = {      # the catalog row's ``config``, every key of it
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def test_the_configuration_is_the_catalogs_with_one_key_cut(manifest):
    body = _body()
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["published"] == {"num_hidden_layers": 32}
    assert body["num_hidden_layers"] == 16
    for key, value in PUBLISHED.items():
        if key == "layer_types":        # the first sixteen: four whole periods
            assert body[key] == value[:16]
        elif key not in body["reduced"]:
            assert body[key] == value, key
    # every published width unchanged
    assert (body["hidden_size"], body["num_attention_heads"],
            body["intermediate_size"], body["linear_key_head_dim"],
            body["linear_value_head_dim"], body["linear_conv_kernel_dim"],
            body["vocab_size"]) == (3840, 30, 11008, 96, 192, 4, 100352)
    assert body["source"] == ("https://huggingface.co/allenai/"
                              "Olmo-Hybrid-7B/blob/main/config.json")
    assert body["reference"] == "benchmark.lib.reference_olmo_hybrid"
    # the three things the catalog's config does not say, and the draws
    for said in ("Olmo 2 / Olmo 3", "on each branch's OUTPUT",
                 "rope_theta null is read as NO rotary",
                 "3,840 / 30 = 128", "have no bias", "log U(1, 16)",
                 "[1e-3, 1e-1]", "arXiv:2412.06464", "beta = 2 sigmoid",
                 "embedding rows at std 1", "float32",
                 "language model only"):
        assert any(said in a for a in body["assumed"]), said
    for said in ("layers 0-15 of 32", "two v5e chips", "4.101 B", "8.20 GB",
                 "215.57 M", "185.81 M", "770.70 M", "2,211,840 B",
                 "26,542,080 B", "[15, 96, 384]", "69,120 B", "15,360 B",
                 "61,440 B", "0.88 GB", "513 pages", "4.03 GB",
                 "13.1 GB of 16", "16 of 32 layers"):
        assert said in body["deployment"], said
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == body["reduced"]
    assert entry["source"] == body["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert manifest["configs"][-1] is entry and len(manifest["configs"]) == 9


def test_the_system_is_told_what_the_file_says():
    from benchmark.lib import system
    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models.transformer import (cache_layers, delta_widths,
                                                  layer_plan)

    body, cfg = _body(), _cfg()
    assert cfg == get_config("olmo-hybrid-7b", num_layers=16)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
            cfg.dims_per_head, cfg.norm_eps, cfg.intermediate_size,
            cfg.vocab_size, cfg.max_seq_len) == (
        body["num_hidden_layers"], body["hidden_size"],
        body["num_attention_heads"], body["num_key_value_heads"], 128,
        body["rms_norm_eps"], body["intermediate_size"], body["vocab_size"],
        body["max_position_embeddings"])
    assert (cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim,
            cfg.linear_conv, cfg.linear_neg_eigval) == (
        body["linear_num_key_heads"], body["linear_key_head_dim"],
        body["linear_value_head_dim"], body["linear_conv_kernel_dim"],
        body["linear_allow_neg_eigval"])
    assert body["linear_num_value_heads"] == body["linear_num_key_heads"]
    assert delta_widths(cfg) == (2880, 5760, 11520)
    assert (cfg.position, cfg.qk_norm, cfg.norm_after, cfg.tie_embeddings,
            cfg.attn_bias, cfg.activation) == (
        "none", True, True, body["tie_word_embeddings"],
        body["attention_bias"], "swiglu")
    # the sixteen layers run are the first sixteen of the published pattern
    kinds = [{"linear_attention": "linear", "full_attention": "full"}[t]
             for t in body["layer_types"]]
    assert list(cfg.layer_pattern[:16]) == kinds
    assert [k for _, _, k, _ in layer_plan(cfg)] == kinds
    assert cache_layers(cfg) == (4, 12)
    # the file's arithmetic: 8.20 GB of weights, 0.88 GB of state, 4.03 GB of
    # K/V in 513 pages
    assert cfg.param_count == 4_100_788_944
    assert round(cfg.param_count * 2 / 1e9, 2) == 8.20
    slots, pages = 32, 1 + 32 * (2048 // 128)
    assert pages == 513
    assert round(slots * 12 * (2_211_840 + 69_120) / 1e9, 2) == 0.88
    assert round(pages * 128 * 61_440 / 1e9, 2) == 4.03
    # the rehearsal: both kinds run, two whole periods, two heads a leaf row
    small = system.transformer_config(body, rehearse=True)
    assert cache_layers(small) == (2, 6)
    assert (small.linear_heads, small.linear_key_dim, small.linear_value_dim,
            small.linear_chunk) == (4, 8, 64, 8)
    assert small.norm_after and small.qk_norm and small.position == "none"


def test_the_traffic_is_what_issue_51_names():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "thinkrollout-backlog.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "serve-backlog"
    assert traffic["engine"] == {"b_slots": 32, "page_size": 128,
                                 "max_model_len": 2048}
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.5, "min": 128,
        "max": 1024}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.4, "min": 256,
        "max": 1024}
    # a slot holds the longest prompt and the longest answer
    assert 1024 + 1024 <= 2048
    par = traffic["parity"]
    # across scan chunks (64) and pages (128), ending inside both
    assert (par["prompt"], par["decode"]) == (700, 16)
    assert par["prompt"] % 64 and par["prompt"] % 128
    assert traffic["drain_seconds"] == 60 and traffic["trace_ticks"] == 60
    # sized on the chip: 1.5 x the 42,252 tokens a window completes + what
    # the 32 slots still owe at its end (the issue's 120-190 was by the roof)
    assert traffic["n_requests"] == 100
    assert traffic["size_seed"] not in (20261047, 20261040, 20260930)
    assert "sized on the chip" in traffic["notes"]
    assert "stop" not in traffic
    assert traffic["rehearse"]["parity"]["prompt"] % 8


def test_the_cell_reports_what_issue_51_lists(manifest):
    reported = {m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", [])}
    assert reported == set(NEW) | OLDER | PR49
    # none of another model's readers
    for m in manifest["per_layer"]:
        if m["name"].startswith(("moe_", "ssm_", "kv_window_", "loop_",
                                 "mla_", "hybrid_")) or m["name"] in (
                "kv_gather_useful_share.capacity",
                "state_layers_traffic_share", "prefill_device_share",
                "window_recomputed_token_share"):
            assert CELL not in m.get("workloads", []), m["name"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, source in (
            ("delta_decode_roofline", "paged forward", "device_trace"),
            ("delta_prefill_roofline", "serving executor", "device_trace"),
            ("delta_step_roofline", "paged forward", "device_trace"),
            ("delta_state_traffic_share", "paged forward",
             "program_counter")):
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == (layer, source, "serve_tokens_per_s", "%",
                                 "higher")
    # appended, in the issue's order, behind everything that was there
    assert [m["name"] for m in manifest["per_layer"]][-4:] == NEW
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["chips"], cell["config"], cell["traffic"]) == (
        CELL, 1, CONFIG, "thinkrollout-backlog")
    assert len(manifest["workloads"]) == 12 and len(cell["why"]) <= 200
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1
    for said in ("512", "768", "32 slots", "12 of 16", "16 of 32"):
        assert said in cell["why"], said
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][-1] == CELL
    assert "workloads" not in e2e["setup_s"]


def test_the_seven_of_pr_49_stand_together_behind_everything_it_found(
        manifest):
    """``test_window_account.py::test_the_manifest_lists_the_cells_of_the_
    table`` line for line but its last, which held the seven as the LAST of
    ``per_layer`` (``pinned_tail.py``): they stand together, in the table's
    order, behind everything PR 49 found, and ahead of this PR's four."""
    from tests.benchmark import test_window_account as table

    kinds = {}
    for cell in manifest["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            kinds[cell["name"]] = json.load(f)["kind"]
    serving = [c for c, k in kinds.items() if k.startswith("serve")]
    backlog = [c for c, k in kinds.items() if k == "serve-backlog"]
    assert len(serving) >= 9 and len(backlog) >= 7
    assert serving[-1] == backlog[-1] == CELL
    entries = {m["name"]: m for m in manifest["per_layer"]}
    want = {**{n: serving for n in table.SERVING},
            **{n: backlog for n in table.BACKLOG},
            **{n: ["ouro-2.6b.mathrollout-backlog"] for n in table.OURO}}
    for name, cells in want.items():
        assert entries[name]["workloads"] == cells, name
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert os.path.isfile(bench_run.reader_path(name))
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(table.NAMES[0])
    assert names[at:at + len(table.NAMES)] == table.NAMES
    assert names[at + len(table.NAMES):] == NEW
    assert at == 55         # the 55 entries PR 49 found, untouched in order


@pytest.mark.parametrize("rule", ["test_keys_names_units",
                                  "test_moves_and_coverage",
                                  "test_files_exist",
                                  "test_config_files_agree_with_what_is_run"])
def test_the_manifest_rules_hold_with_the_twelfth_cell(manifest, rule):
    from tests.benchmark import test_manifest

    getattr(test_manifest, rule)(manifest)


# ------------------------------------------- delta_work and the four readers

def test_delta_work_arithmetic():
    from benchmark.lib import delta_work as W
    from benchmark.lib import ssm_moe_work, ssm_work
    from deepspeed_tpu.models import get_config

    cfg = _cfg()
    assert W.applies(cfg) and W.layer_counts(cfg) == (12, 4)
    # its readers stay off the Mamba-2 models, and theirs off this one
    for other in (get_config("falcon-h1-34b", num_layers=5),
                  get_config("granite-4.0-h-small", num_layers=10),
                  get_config("opt-1.3b")):
        assert not W.applies(other)
    assert not ssm_moe_work.applies(cfg) and not ssm_work.is_ssm(cfg)
    assert W.conv_channels(cfg) == 11_520
    assert W.state_bytes(cfg) == 30 * 96 * 192 * 4 == 2_211_840
    assert 12 * W.state_bytes(cfg) == 26_542_080        # a slot
    assert W.tail_bytes(cfg) == 3 * 11_520 * 2 == 69_120
    assert W.kv_row_bytes(cfg) == 15_360
    assert 4 * W.kv_row_bytes(cfg) == 61_440            # a token
    # the five projections 88.47 M, b and a 0.23 M, the rest 0.05 M
    assert W.mixer_matmul_params(cfg) == (3840 * 17_280 + 5760 * 3840
                                          + 3840 * 60) == 88_704_000
    assert W.mixer_params(cfg) == 88_704_000 + 4 * 11_520 + 60 + 192
    assert W.attention_matmul_params(cfg) == 4 * 3840 * 3840 == 58_982_400
    assert W.mlp_params(cfg) == 3 * 3840 * 11_008 == 126_812_160
    assert W.layer_params(cfg, "linear") == 215_570_172
    assert W.layer_params(cfg, "full") == 185_809_920
    assert W.head_params(cfg) == 100_352 * 3840 + 3840 == 385_355_520
    assert W.held_params(cfg) == cfg.param_count == 4_100_788_944
    assert W.streamed_params(cfg) == 4_100_788_944 - 385_351_680
    # a tick of 32 live slots at 768 rows each: 7.43 GB of weights, 1.75 GB
    # of state and tails read and written, 1.51 GB of K/V: 10.69 GB, 13.1 ms
    # at 819 GB/s (the issue's 13.0 counted no tail)
    work = W.decode_tick_work(cfg, 32, 32 * 768 * 4)
    assert work["weight_bytes"] == 2 * W.streamed_params(cfg)
    assert round(work["weight_bytes"] / 1e9, 2) == 7.43
    assert work["state_bytes"] == 2 * 32 * 12 * (2_211_840 + 69_120)
    assert round(work["state_bytes"] / 1e9, 2) == 1.75
    assert work["kv_bytes"] == 32 * 768 * 61_440
    assert round(work["kv_bytes"] / 1e9, 2) == 1.51
    total = sum(v for k, v in work.items() if k.endswith("_bytes"))
    assert round(total / 819e9 * 1e3, 1) == 13.1
    assert 16 < 100 * work["state_bytes"] / total < 17
    # the bytes bound a tick, not the operations (32 rows a weight)
    assert work["flops"] / 197e12 < 0.15 * total / 819e9
    assert W.tick_bytes(cfg, dict(state_slots=32, kv_live_rows=98_304)) == {
        k: v for k, v in work.items() if k != "flops"}
    assert W.step_bytes(cfg, 32) == 2 * 32 * 26_542_080
    # one token: two operations a layer's matmul parameter, the head once
    one = W.prefill_flops(cfg, 1)
    matmul = 12 * (88_704_000 + 126_812_160) + 4 * (58_982_400 + 126_812_160)
    assert one == pytest.approx(
        2 * matmul + 2 * 100_352 * 3840 + 4 * 4 * 30 * 128
        + 12 * (7 * 30 * 96 * 192 + 2 * 4 * 11_520))
    # a median prompt of 512: ~3.4 TFLOP, 17 ms at the chip's peak; the
    # recurrence 0.7% of it, attention's triangle 0.2%
    prompt = W.prefill_flops(cfg, 512)
    assert 3.3e12 < prompt < 3.5e12
    assert 512 * 12 * W.recurrence_ops(cfg) < 0.01 * prompt


def _record():
    cfg = _cfg()
    tick = dict(live_rows=24_000, state_slots=32, state_layers=12,
                kv_layers=4, kv_live_rows=96_128, state_bytes=1,
                state_passes=1)
    spans = [
        _span("serve.decode", 1.0, **tick),
        _span("serve.decode", 1.1, **dict(tick, kv_live_rows=96_256)),
        _span("serve.decode", 9.0, **dict(tick, state_slots=2)),    # drain
        _span("serve.prefill", 1.2, tokens=500, bucket=512, scan_chunks=8,
              scan_chunks_bucket=8, state_reset=1),
        _span("serve.prefill", 1.3, tokens=900, bucket=1024, scan_chunks=15,
              scan_chunks_bucket=16, state_reset=1),
    ]
    host = [[10, 30, "serve.decode"], [100, 30, "serve.decode"],
            [200, 400, "serve.prefill"], [700, 200, "serve.prefill"]]
    modules = [[12, 16_000_000, "jit_serve_decode"],
               [102, 18_000_000, "jit_serve_decode"],
               [210, 40_000_000, "jit_serve_prefill_512"],
               [710, 75_000_000, "jit_serve_prefill_1024"]]
    per_op = {"jit_serve_decode@serve.decode:delta_step.12 custom-call "
              "(f32[384,15,96,384]": 0.0030,
              "jit_serve_decode@serve.decode:delta_step.13 custom-call "
              "(f32[384,15,96,384]": 0.0032,
              "jit_serve_decode@serve.decode:fusion.7 fusion bf16[32,3840]":
                  0.02,
              "jit_serve_prefill_512@serve.prefill:delta_step.1 custom-call "
              "f32": 0.5}
    trace = {"modules": modules, "host": host, "busy_s": 0.15,
             "per_op_s": per_op}
    return {"serve": {"cfg": cfg, "t_end": 5.0}, "spans": spans,
            "trace": trace, "device": {"kind": "TPU v5 lite"}}


def test_the_four_readers_on_a_hand_built_record():
    from benchmark.lib import delta_work as W

    rec = _record()
    cfg = rec["serve"]["cfg"]
    w = [W.tick_bytes(cfg, dict(state_slots=32, kv_live_rows=r))
         for r in (96_128, 96_256)]
    state = sum(x["state_bytes"] for x in w)
    assert _read("delta_state_traffic_share", rec) == pytest.approx(
        100 * state / sum(sum(x.values()) for x in w))
    assert 16 < _read("delta_state_traffic_share", rec) < 17
    mean = W.decode_tick_work(cfg, 32, 96_192)
    nbytes = sum(v for k, v in mean.items() if k.endswith("_bytes"))
    assert _read("delta_decode_roofline", rec) == pytest.approx(
        100 * (nbytes / 819e9) / 17e-3)
    ops = W.prefill_flops(cfg, 500) + W.prefill_flops(cfg, 900)
    assert _read("delta_prefill_roofline", rec) == pytest.approx(
        100 * (ops / 197e12) / 0.115)
    # the kernel's ops of the decode program alone: 6.2 ms over two ticks
    assert _read("delta_step_roofline", rec) == pytest.approx(
        100 * (2 * W.step_bytes(cfg, 32) / 819e9) / 0.0062)
    for name in NEW:
        assert 0 < _read(name, rec) <= 100, name


@pytest.mark.parametrize("name", NEW)
def test_the_readers_read_nothing_from_a_record_without_the_attrs(name):
    """Another model's configuration has no "linear" layer (the parent's
    programs: every cell the benchmark had); spans without the per-kind
    counts; no trace, no device metric; a tick that holds the plain step has
    no kernel to time."""
    from deepspeed_tpu.models import get_config

    rec = _record()
    for other in ("falcon-h1-34b", "granite-4.0-h-small", "opt-1.3b"):
        cfg = get_config(other, num_layers=5)
        assert _read(name, dict(rec, serve={"cfg": cfg})) is None
    assert _read(name, dict(rec, serve={})) is None
    old = [_span(s.name, s.t0, **{k: v for k, v in s.attrs.items()
                                  if k in ("live_rows", "tokens", "bucket")})
           for s in rec["spans"]]
    assert _read(name, dict(rec, spans=old)) is None
    assert _read(name, dict(rec, spans=[])) is None
    if name != "delta_state_traffic_share":
        assert _read(name, dict(rec, trace=None)) is None
    if name == "delta_step_roofline":
        plain = dict(rec["trace"], per_op_s={
            k: v for k, v in rec["trace"]["per_op_s"].items()
            if "jit_serve_decode" not in k or "delta_step" not in k})
        assert _read(name, dict(rec, trace=plain)) is None
    if name == "delta_prefill_roofline":
        no_prefill = dict(rec["trace"], modules=rec["trace"]["modules"][:2],
                          host=rec["trace"]["host"][:2])
        assert _read(name, dict(rec, trace=no_prefill)) is None


# ------------------------------------------------------------ end to end

def test_rehearse_the_cell(capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 51),
                             "--seconds", "1.5", "--trace", "1", "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"delta_state_traffic_share", "slots_active_mean",
            "window_compiles.serve", "prefill_pad_share",
            "tick_host_ms_p50.capacity", "admit_host_ms_p50",
            "host_busy_share", "window_tokens_per_tick"} <= set(
        res["metric_names"])
    # no device metric from a CPU run, and none of another model's readers
    assert not {n for n in res["metric_names"] if "roofline" in n}
    assert not {n for n in res["metric_names"]
                if n.startswith(("ssm_", "moe_", "state_layers"))}
    checks = _last(out, "checks")
    assert checks["logits_match_reference"]
    assert checks["layers_match_reference"] and checks["pages_balanced"]
    layers = [json.loads(ln[5:]) for ln in out.splitlines()
              if ln.startswith("note ") and "layer_checks" in ln][-1]
    assert {"linear_layer_block", "attention_layer_block",
            "state_after_prefill", "state_after_decode",
            "logits_after_decode"} <= set(layers["layer_checks"])
