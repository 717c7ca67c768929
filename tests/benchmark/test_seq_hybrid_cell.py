"""The cell of ISSUE 47 (``granite-4.0-h-small-ep2-d10.ragdoc-backlog``): the
configuration's file against the catalog row and what it says it cut,
``lib/ssm_moe_work.py``'s arithmetic by hand, the four readers on hand-built
records, the manifest's rules with the eleventh cell, and the cell end to end
at a tiny size on the CPU (``--rehearse --trace 1``)."""
import json
import os
import types

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "granite-4.0-h-small-ep2-d10"
CELL = CONFIG + ".ragdoc-backlog"
NEW = {"ssm_moe_decode_roofline", "ssm_moe_prefill_roofline",
       "state_layers_traffic_share", "prefill_device_share"}
HELD = ("moe_local_pair_share", "moe_held_touched_share")


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
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352}


def test_the_configuration_is_the_catalogs_with_three_keys_cut(manifest):
    body = _body()
    assert body["reduced"] == ["num_hidden_layers", "num_local_experts",
                               "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 40,
                                 "num_local_experts": 72,
                                 "vocab_size": 100352}
    assert (body["num_hidden_layers"], body["num_local_experts"],
            body["vocab_size"]) == (10, 36, 50176)
    for key, value in PUBLISHED.items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    # the source's own key counts the experts; the pattern is kept whole
    assert "n_routed_experts" not in body and len(body["layer_types"]) == 40
    assert body["source"] == ("https://huggingface.co/ibm-granite/"
                              "granite-4.0-h-small/blob/main/config.json")
    assert body["reference"] == "benchmark.lib.reference_granite4h"
    assert len(body["assumed"]) >= 10
    for said in ("ONE routed expert's width", "4,096 / 32 = 128", "[z | xBC | dt]",
                 "log U(1, 16)", "[1e-3, 1e-1]", "D = 1", "float32",
                 "drawn from the slice", "language model only", "nope"):
        assert any(said in a for a in body["assumed"]), said
    for said in ("8 v5e chips", "4 pipeline stages of 2",
                 "layers shared by 2 chips", "36 a chip", "102.3 M",
                 "461.2 M", "400.9 M", "205.5 M", "4.757 B", "9.51 GB",
                 "4,244,992 B", "4,096 B", "1.22 GB", "3,329 pages",
                 "1.75 GB", "12.5 GB of 16", "4.4 rows", "8.9"):
        assert said in body["deployment"], said
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == body["reduced"]
    assert entry["source"] == body["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_system_is_told_what_the_file_says():
    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models.transformer import (cache_layers, layer_plan,
                                                  ssm_in_width)

    body, cfg = _body(), _cfg()
    assert cfg == get_config("granite-4.0-h-small", num_layers=10,
                             moe_experts_held=36, moe_expert_first=0,
                             vocab_size=50176)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
            cfg.dims_per_head, cfg.norm_eps) == (
        body["num_hidden_layers"], body["hidden_size"],
        body["num_attention_heads"], body["num_key_value_heads"], 128,
        body["rms_norm_eps"])
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (
        body["mamba_n_heads"], body["mamba_d_head"], body["mamba_d_state"],
        body["mamba_n_groups"], body["mamba_d_conv"],
        body["mamba_chunk_size"])
    assert cfg.ssm_heads * cfg.ssm_head_dim == (
        body["mamba_expand"] * body["hidden_size"])
    assert ssm_in_width(cfg) == 16768
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.moe_expert_first,
            cfg.moe_top_k, cfg.intermediate_size, cfg.moe_shared_experts
            * cfg.intermediate_size) == (
        72, body["num_local_experts"], 0, body["num_experts_per_tok"],
        body["intermediate_size"], body["shared_intermediate_size"])
    assert (cfg.moe_score_func, cfg.moe_norm_topk_prob,
            cfg.moe_drop_tokens) == ("softmax", True, False)
    # the four multipliers, each onto the field that fits
    assert (cfg.embed_multiplier, cfg.attn_softmax_scale,
            cfg.residual_multiplier, 1 / cfg.lm_head_multiplier) == (
        body["embedding_multiplier"], body["attention_multiplier"],
        body["residual_multiplier"], body["logits_scaling"])
    assert (cfg.position, cfg.tie_embeddings) == ("none", True)
    # the ten layers run are the first ten of the published forty
    kinds = [{"mamba": "ssm", "attention": "full"}[t]
             for t in body["layer_types"]]
    assert list(cfg.layer_pattern) == kinds
    assert [k for _, _, k, _ in layer_plan(cfg)] == kinds[:10]
    assert cache_layers(cfg) == (1, 9)
    # the file's arithmetic: 9.51 GB of weights, 1.22 GB of state, 1.75 GB
    # of K/V in 3,329 pages
    assert round(cfg.param_count * 2 / 1e9, 2) == 9.51
    slots, pages = 32, 1 + 32 * (13312 // 128)
    assert pages == 3329
    assert round(slots * 9 * 4_244_992 / 1e9, 2) == 1.22
    assert round(pages * 128 * 4096 / 1e9, 2) == 1.75
    # the rehearsal: both kinds run, 6 of 12 experts held top 3, every one
    # of the four multipliers away from what a model without it has
    from benchmark.lib import system

    small = system.transformer_config(body, rehearse=True)
    assert cache_layers(small) == (1, 9)
    assert (small.num_experts, small.moe_experts_held, small.moe_top_k) == (
        12, 6, 3)
    assert small.ssm_heads * small.ssm_head_dim == 2 * small.hidden_size
    assert 1.0 not in (small.embed_multiplier, small.residual_multiplier,
                       small.lm_head_multiplier)
    assert small.attn_softmax_scale not in (None, small.dims_per_head ** -0.5)


def test_the_traffic_is_what_issue_47_names():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "ragdoc-backlog.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "serve-backlog"
    assert traffic["engine"] == {"b_slots": 32, "page_size": 128,
                                 "max_model_len": 13312}
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.5, "min": 2048,
        "max": 12288}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64,
        "max": 768}
    # a slot holds the longest prompt and the longest answer
    assert 12288 + 768 <= 13312
    par = traffic["parity"]
    # across a scan chunk (256), a page (128) and ending inside both
    assert par["prompt"] >= 3000 and par["decode"] >= 16
    assert par["prompt"] % 256 and par["prompt"] % 128
    assert traffic["drain_seconds"] == 60 and traffic["trace_ticks"] == 60
    assert traffic["size_seed"] not in (20261040, 20260930)
    assert "sized on the chip" in traffic["notes"]
    assert traffic["rehearse"]["parity"]["prompt"] % 8


def test_the_cell_reports_what_issue_47_lists(manifest):
    reported = {m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", [])}
    assert reported == NEW | {
        "slots_active_mean", "prefill_ms_p50", "window_compiles.serve",
        "device_idle_share.serve", "peak_hbm_gb.serve",
        "host_bound_idle_share.serve", "admit_host_ms_p50",
        "prefill_pad_share", "decode_ms_p50.capacity",
        "tick_host_ms_p50.capacity", "gap_fetch_share", "gap_host_share",
        "gap_launch_share", "host_busy_share", "moe_expert_matmul_share"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, source in (
            ("ssm_moe_decode_roofline", "paged forward", "device_trace"),
            ("ssm_moe_prefill_roofline", "serving executor", "device_trace"),
            ("state_layers_traffic_share", "paged forward",
             "program_counter"),
            ("prefill_device_share", "serving executor", "device_trace")):
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == (layer, source, "serve_tokens_per_s", "%",
                                 "higher")
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "ragdoc-backlog")
    for said in ("6144", "256", "32 slots", "4.4 rows", "8.9", "10 of 40"):
        assert said in cell["why"], said
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]


def test_the_tenth_cell_still_reports_what_it_listed(manifest):
    """``test_loop_cell.py`` asks this of the tenth cell together with its
    POSITION in the lists (expected to fail since this cell was appended
    behind it: ``conftest.py``); what it reports is held here by name."""
    from tests.benchmark import test_loop_cell as tenth

    reported = {m["name"] for m in manifest["per_layer"]
                if tenth.CELL in m.get("workloads", [])}
    assert reported == set(tenth.NEW) | tenth.SHARED
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in tenth.NEW:
        assert by_name[name]["workloads"] == [tenth.CELL]
    # what each PR added stays in the order it was added (entries are only
    # ever appended), wherever later PRs' entries come to stand
    names = [m["name"] for m in manifest["per_layer"]]
    at = [names.index(n) for n in tenth.NEW]
    assert at == sorted(at) and at[-1] - at[0] == len(at) - 1


def test_readers_that_assume_another_model_keep_their_lists(manifest):
    """The held-share readers key on ``n_routed_experts`` under ``reduced``,
    which this configuration (whose source counts experts under
    ``num_local_experts``) does not have; the ``ssm_*`` readers reckon a
    mixer AND attention in every block; the two-pool readers a window."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in HELD + ("ssm_state_traffic_share", "ssm_decode_roofline",
                        "ssm_prefill_roofline", "kv_window_rows_share",
                        "kv_full_read_useful_share",
                        "hybrid_decode_roofline",
                        "kv_gather_useful_share.capacity"):
        assert CELL not in by_name[name]["workloads"], name
    assert "n_routed_experts" not in _body()["reduced"]


@pytest.mark.parametrize("rule", ["test_keys_names_units",
                                  "test_moves_and_coverage",
                                  "test_files_exist",
                                  "test_config_files_agree_with_what_is_run"])
def test_the_manifest_rules_hold_with_the_eleventh_cell(manifest, rule):
    from tests.benchmark import test_manifest

    getattr(test_manifest, rule)(manifest)


# ----------------------------------------- ssm_moe_work and the four readers

def test_ssm_moe_work_arithmetic():
    from benchmark.lib import ssm_moe_work as W
    from benchmark.lib import ssm_work

    cfg = _cfg()
    assert W.applies(cfg) and W.layer_counts(cfg) == (9, 1)
    # the mixer: W_in 4,096 x 16,768, W_out 8,192 x 4,096, the convolution
    # with its bias, A, D, dt's bias, the gated norm
    assert W.mixer_params(cfg) == (4096 * 16768 + 8192 * 4096 + 8448 * 5
                                   + 3 * 128 + 8192) == 102_286_976
    assert round(W.mixer_params(cfg) / 1e6, 1) == 102.3
    assert W.attention_params(cfg) == 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert W.expert_params(cfg) == 3 * 4096 * 768 == 9_437_184
    assert W.shared_params(cfg) == 3 * 4096 * 1536
    assert W.layer_params(cfg, "ssm") == 461_203_072
    assert round(W.layer_params(cfg, "ssm") / 1e6, 1) == 461.2
    assert round(W.layer_params(cfg, "full") / 1e6, 1) == 400.9
    assert W.head_params(cfg) == 50176 * 4096 + 4096
    assert W.held_params(cfg) == cfg.param_count == 4_757_211_776
    assert round(W.held_params(cfg) * 2 / 1e9, 2) == 9.51
    assert W.dense_params(cfg) == W.held_params(cfg) - 10 * 36 * 9_437_184
    assert ssm_work.state_bytes(cfg) == 4_244_992
    assert ssm_work.kv_row_bytes(cfg) == 4_096
    # a tick of 32 live slots at 6,500 rows each that touches every held
    # expert: 9.51 GB of weights, 2.44 GB of state, 0.85 GB of K/V
    work = W.decode_tick_work(cfg, 360, 32, 32 * 6500)
    assert work["weight_bytes"] == 2 * W.held_params(cfg)
    assert work["state_bytes"] == 2 * 32 * 9 * 4_244_992
    assert round(work["state_bytes"] / 1e9, 2) == 2.45
    assert work["kv_bytes"] == 32 * 6500 * 4096
    assert round(sum(work.values()) / 819e9 * 1e3, 1) == 15.6
    # an expert no row reached is not streamed
    assert (work["weight_bytes"] - W.decode_tick_work(
        cfg, 350, 32, 0)["weight_bytes"]) == 10 * 2 * 9_437_184
    # one token that lands five of its ten pairs a layer on held experts
    one = W.prefill_flops(cfg, 1, 50)
    dense = (9 * (4096 * 16768 + 8192 * 4096) + 2 * 4096 * 4096
             + 2 * 4096 * 1024 + 10 * (3 * 4096 * 1536 + 4096 * 72))
    assert one == pytest.approx(
        2 * dense + 2 * 50 * 9_437_184 + 2 * 50176 * 4096
        + 4 * 32 * 128 + 9 * (5 * 128 * 64 * 128 + 2 * 4 * 8448))
    assert 3.2e9 < one - 2 * 50176 * 4096 < 3.4e9       # ~3.3 GFLOP a token
    # a median prompt: ~21 TFLOP, attention's triangle 1.5% of it
    prompt = W.prefill_flops(cfg, 6144, 6144 * 50)
    assert 20e12 < prompt < 22e12
    tri = 6144 * 6145 / 2 * 4 * 32 * 128
    assert prompt == pytest.approx(6144 * (one - 2 * 50176 * 4096 - 4 * 32 * 128)
                                   + 2 * 50176 * 4096 + tri)


def _record():
    cfg = _cfg()
    tick = dict(live_rows=200_000, state_slots=32, state_layers=9,
                kv_layers=1, kv_live_rows=200_032, state_bytes=1,
                experts_touched_held=358, pairs_held=1600, pairs_total=3200)
    spans = [
        _span("serve.decode", 1.0, **tick),
        _span("serve.decode", 1.1, **dict(tick, experts_touched_held=360)),
        _span("serve.decode", 9.0, **dict(tick, state_slots=2)),    # drain
        _span("serve.prefill", 1.2, tokens=6000, bucket=8192,
              scan_chunks=24, pairs_held=300_000, pairs_total=600_000),
        _span("serve.prefill", 1.3, tokens=2500, bucket=4096,
              scan_chunks=10, pairs_held=125_000, pairs_total=250_000),
    ]
    host = [[10, 30, "serve.decode"], [100, 30, "serve.decode"],
            [200, 400, "serve.prefill"], [700, 200, "serve.prefill"]]
    modules = [[12, 20_000_000, "jit_serve_decode"],
               [102, 22_000_000, "jit_serve_decode"],
               [210, 150_000_000, "jit_serve_prefill_8192"],
               [710, 70_000_000, "jit_serve_prefill_4096"]]
    trace = {"modules": modules, "host": host, "busy_s": 0.3,
             "per_op_s": {}}
    return {"serve": {"cfg": cfg, "t_end": 5.0}, "spans": spans,
            "trace": trace, "device": {"kind": "TPU v5 lite"}}


def test_the_four_readers_on_a_hand_built_record():
    from benchmark.lib import ssm_moe_work as W

    rec = _record()
    cfg = rec["serve"]["cfg"]
    w = [W.decode_tick_work(cfg, e, 32, 200_032) for e in (358, 360)]
    state = sum(x["state_bytes"] for x in w)
    assert _read("state_layers_traffic_share", rec) == pytest.approx(
        100 * state / sum(sum(x.values()) for x in w))
    assert 17 < _read("state_layers_traffic_share", rec) < 21
    mean = W.decode_tick_work(cfg, 359, 32, 200_032)
    assert _read("ssm_moe_decode_roofline", rec) == pytest.approx(
        100 * (sum(mean.values()) / 819e9) / 21e-3)
    ops = W.prefill_flops(cfg, 6000, 300_000) + W.prefill_flops(
        cfg, 2500, 125_000)
    assert _read("ssm_moe_prefill_roofline", rec) == pytest.approx(
        100 * (ops / 197e12) / 0.22)
    assert _read("prefill_device_share", rec) == pytest.approx(
        100 * 0.22 / 0.3)
    for name in NEW:
        assert 0 < _read(name, rec) <= 100, name


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_readers_read_nothing_from_a_program_without_the_counters(name):
    """The parent's spans of a state model carry ``state_slots`` and none of
    ``state_layers`` / ``experts_touched_held`` / ``pairs_held``; another
    model's configuration has no "ssm" layer; no trace, no device metric."""
    from deepspeed_tpu.models import get_config

    rec = _record()
    old = [_span(s.name, s.t0, **{k: v for k, v in s.attrs.items()
                                  if k in ("live_rows", "state_slots",
                                           "tokens", "bucket",
                                           "scan_chunks")})
           for s in rec["spans"]]
    if name != "prefill_device_share":
        assert _read(name, dict(rec, spans=old)) is None
        other = dict(rec, serve={"cfg": get_config("falcon-h1-34b",
                                                   num_layers=5)})
        assert _read(name, other) is None
        assert _read(name, dict(rec, spans=[])) is None
    if name != "state_layers_traffic_share":
        assert _read(name, dict(rec, trace=None)) is None
    no_prefill = dict(rec["trace"], modules=rec["trace"]["modules"][:2],
                      host=rec["trace"]["host"][:2])
    if "prefill" in name:
        assert _read(name, dict(rec, trace=no_prefill)) is None


# ------------------------------------------------------------ end to end

def test_rehearse_the_cell(capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 47),
                             "--seconds", "1.5", "--trace", "1", "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"state_layers_traffic_share", "slots_active_mean",
            "window_compiles.serve", "prefill_pad_share",
            "tick_host_ms_p50.capacity", "admit_host_ms_p50",
            "host_busy_share", "cache_misses"} <= set(res["metric_names"])
    # no device metric from a CPU run, and none of another model's readers
    assert not {n for n in res["metric_names"] if "roofline" in n}
    assert not {"ssm_state_traffic_share", "moe_local_pair_share",
                "prefill_device_share"} & set(res["metric_names"])
    checks = _last(out, "checks")
    assert checks["logits_match_reference"]
    assert checks["layers_match_reference"] and checks["pages_balanced"]
    layers = [json.loads(ln[5:]) for ln in out.splitlines()
              if ln.startswith("note ") and "layer_checks" in ln][-1]
    assert {"state_layer_block", "attention_layer_block",
            "expert_layer"} <= set(layers["layer_checks"])
