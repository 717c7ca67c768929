"""The cell of ISSUE 54 (``lfm2-8b-a1b-d14.agentturn-backlog``): the
configuration's file against the catalog row and what it says it cut, its
bytes reckoned again, ``lib/conv_moe_work.py``'s arithmetic by hand, the
three readers on hand-built records, the manifest's rules READ AS RULES with
one more cell (no count of cells or configurations, no "is last"), every
other assertion of the tests that ``pinned_thirteenth.py`` sets aside, and
the cell end to end at a tiny size on the CPU (``--rehearse --trace 1``)."""
import json
import os
import types

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "lfm2-8b-a1b-d14"
TRAFFIC = "agentturn-backlog"
CELL = CONFIG + "." + TRAFFIC
NEW = ["conv_moe_decode_roofline", "conv_moe_prefill_roofline",
       "moe_rows_an_expert"]
# readers of another model's cell that this one joins because they return a
# number for it (one reader a quantity, no second name)
JOINED = {"moe_expert_matmul_share", "moe_live_row_share",
          "kv_gather_useful_share.capacity"}
OLMO = "olmo-hybrid-7b-d16.thinkrollout-backlog"


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def _body():
    return _json("configs", CONFIG + ".json")


def _cfg():
    from benchmark.lib import system

    return system.transformer_config(_body(), rehearse=False)


def _kinds(manifest):
    return {c["name"]: _json("traffic", c["traffic"] + ".json")["kind"]
            for c in manifest["workloads"]}


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
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 4 + [
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def test_the_configuration_is_the_catalogs_with_one_key_cut(manifest):
    body = _body()
    assert len(PUBLISHED["layer_types"]) == 24
    assert [i for i, t in enumerate(PUBLISHED["layer_types"])
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["published"] == {"num_hidden_layers": 24}
    assert body["num_hidden_layers"] == 14
    for key, value in PUBLISHED.items():
        if key not in body["reduced"]:
            assert body[key] == value, key     # the pattern copied whole
    # no width, no expert count and no vocabulary cut
    assert not {"num_experts", "vocab_size", "hidden_size",
                "moe_intermediate_size", "num_experts_per_tok"} & set(
        body["reduced"])
    assert body["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                              "blob/main/config.json")
    assert body["reference"] == "benchmark.lib.reference_lfm2"
    for said in ("2,048 / 32 = 64", "the embedding transposed",
                 "8,340 M", "operator_norm", "NO activation",
                 "conv_L_cache - 1 = 2 rows", "q_layernorm",
                 "sum + 1e-6", "expert_bias is zeros", "U(-1/2, 1/2)",
                 "tail bfloat16", "float32", "whole vocabulary",
                 "3,072 positions"):
        assert any(said in a for a in body["assumed"]), said
    for said in ("first of two pipeline stages", "two v5e chips", "16.7 GB",
                 "layers 0-13 of 24", "three whole periods",
                 "3 attention and 11 conv", "second stage", "16.78 M",
                 "10.49 M", "44.04 M", "352.39 M", "134.22 M", "4,667 M",
                 "9.33 GB", "6,144 B", "8,192 B", "90,112 B", "3,073 pages",
                 "2.42 GB", "11.5 MB", "11.8 GB of 16", "14 of 24 layers"):
        assert said in body["deployment"], said
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == body["reduced"]
    assert entry["source"] == body["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # one configuration a file, one file a configuration
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))


def test_the_system_is_told_what_the_file_says():
    from benchmark.lib import system
    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models.transformer import (cache_layers, layer_groups,
                                                  layer_plan)

    body, cfg = _body(), _cfg()
    assert cfg == get_config("lfm2-8b-a1b", num_layers=14)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
            cfg.dims_per_head, cfg.norm_eps, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.vocab_size, cfg.max_seq_len,
            cfg.rope_theta) == (
        body["num_hidden_layers"], body["hidden_size"],
        body["num_attention_heads"], body["num_key_value_heads"], 64,
        body["norm_eps"], body["intermediate_size"],
        body["moe_intermediate_size"], body["vocab_size"],
        body["max_position_embeddings"], body["rope_theta"])
    assert (cfg.conv_taps, cfg.conv_bias, cfg.dense_layers, cfg.num_experts,
            cfg.moe_top_k, cfg.moe_norm_topk_prob, cfg.moe_routed_scale,
            cfg.moe_select_bias) == (
        body["conv_L_cache"], body["conv_bias"], body["num_dense_layers"],
        body["num_experts"], body["num_experts_per_tok"],
        body["norm_topk_prob"], body["routed_scaling_factor"],
        body["use_expert_bias"])
    assert (cfg.moe_experts_held, cfg.moe_shared_experts, cfg.qk_norm,
            cfg.tie_embeddings, cfg.moe_score_func) == (
        None, 0, "head", True, "sigmoid")
    # the fourteen layers run are the first fourteen of the published pattern
    kinds = [{"conv": "conv", "full_attention": "full"}[t]
             for t in body["layer_types"][:14]]
    assert list(cfg.layer_pattern[:14]) == kinds
    assert [k for _, _, k, _ in layer_plan(cfg)] == kinds
    assert kinds[2:] == ["full", "conv", "conv", "conv"] * 3
    assert {g: n for g, (_, n) in layer_groups(cfg).items()} == {
        "conv_dense": 2, "full_moe": 3, "conv_moe": 9}
    assert cache_layers(cfg) == (3, 11)
    # the file's arithmetic, reckoned again
    conv, attn = 2048 * 6144 + 2048 * 2048 + 3 * 2048, 2 * 2048 * (2048 + 512)
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    moe = 32 * expert + 2048 * 32 + 32
    assert [round(x / 1e6, 2) for x in (conv, attn, dense, moe,
                                        65536 * 2048)] == [
        16.78, 10.49, 44.04, 352.39, 134.22]
    norms = 14 * 2 * 2048 + 2048 + 3 * 2 * 64
    total = 11 * conv + 3 * attn + 2 * dense + 12 * moe + 65536 * 2048 + norms
    assert cfg.param_count == total == 4_667_077_376
    assert round(total / 1e6) == 4667 and round(total * 2 / 1e9, 2) == 9.33
    assert get_config("lfm2-8b-a1b").param_count == 8_339_930_560
    slots, pages = 128, 1 + 128 * (3072 // 128)
    assert pages == 3073
    token, tail = 3 * 2 * 8 * 64 * 2, 11 * 2 * 2048 * 2
    assert (token, tail) == (6144, 90_112)
    assert round(pages * 128 * token / 1e9, 2) == 2.42
    assert round(slots * tail / 1e6, 1) == 11.5
    assert round((total * 2 + pages * 128 * token + slots * tail) / 1e9,
                 1) == 11.8
    # the rehearsal: all three groups, two whole periods
    small = system.transformer_config(body, rehearse=True)
    assert {g: n for g, (_, n) in layer_groups(small).items()} == {
        "conv_dense": 2, "full_moe": 2, "conv_moe": 6}
    assert small.qk_norm == "head" and small.conv_taps == 3


def test_the_traffic_is_what_issue_54_names():
    traffic = _json("traffic", TRAFFIC + ".json")
    assert traffic["kind"] == "serve-backlog"
    assert traffic["engine"] == {"b_slots": 128, "page_size": 128,
                                 "max_model_len": 3072}
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.6, "min": 128,
        "max": 2048}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.5, "min": 64,
        "max": 1024}
    # a slot holds the longest prompt and the longest answer
    assert 2048 + 1024 <= 3072
    par = traffic["parity"]
    assert (par["prompt"], par["decode"]) == (700, 16)
    assert par["prompt"] % 128      # the prompt ends inside a page
    assert traffic["drain_seconds"] >= 60 and traffic["trace_ticks"] == 60
    others = {_json("traffic", f)["size_seed"]
              for f in os.listdir(os.path.join(ROOT, "benchmark", "traffic"))
              if f != TRAFFIC + ".json"
              and "size_seed" in _json("traffic", f)}
    assert traffic["size_seed"] not in others
    assert "sized on the chip" in traffic["notes"]
    assert traffic["rehearse"]["engine"]["b_slots"] >= 3


# ------------------------------------------------- the manifest, as rules

def test_the_cell_is_listed_under_every_reader_a_backlog_cell_lists(manifest):
    """Whatever every OTHER serve-backlog cell reports, this one reports; so
    with ``serve_tokens_per_s``; and what it reports beyond that is its own
    three and the readers it joined."""
    kinds = _kinds(manifest)
    backlog = [c for c, k in kinds.items() if k == "serve-backlog"]
    assert CELL in backlog and kinds[CELL] == "serve-backlog"
    others = [c for c in backlog if c != CELL]
    assert len(others) >= 2
    common = [m["name"] for m in manifest["per_layer"]
              if all(c in m.get("workloads", []) for c in others)]
    assert {"gap_fetch_share", "gap_host_share", "gap_launch_share",
            "host_busy_share", "slots_active_mean", "window_compiles.serve",
            "device_idle_share.serve", "peak_hbm_gb.serve",
            "host_bound_idle_share.serve", "prefill_pad_share",
            "prefill_ms_p50", "admit_host_ms_p50", "decode_ms_p50.capacity",
            "tick_host_ms_p50.capacity", "window_tokens_per_tick",
            "window_decode_time_share", "window_prefill_time_share",
            "window_tick_ms_mean"} <= set(common)
    reported = {m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", [])}
    assert set(common) <= reported
    assert reported - set(common) == set(NEW) | JOINED
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    for name in ("tpot_p50_ms", "train_tokens_per_s_chip"):
        assert CELL not in e2e[name]["workloads"]
    # what reads the wrong width or depth for this model is not joined
    # (PERF.md section 7), nor another kind of state's readers
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("moe_experts_touched_share", "moe_expert_matmul_roofline",
                 "moe_decode_roofline", "state_layers_traffic_share",
                 "ssm_moe_decode_roofline", "delta_decode_roofline",
                 "prefill_device_share", "moe_local_pair_share",
                 "moe_held_touched_share"):
        assert CELL not in by_name[name]["workloads"], name


def test_the_new_entries_and_the_cell_are_what_issue_54_names(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, source, unit in (
            ("conv_moe_decode_roofline", "paged forward", "device_trace", "%"),
            ("conv_moe_prefill_roofline", "serving executor", "device_trace",
             "%"),
            ("moe_rows_an_expert", "expert layer", "program_counter",
             "rows")):
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == (layer, source, "serve_tokens_per_s", unit,
                                 "higher")
        assert os.path.isfile(bench_run.reader_path(name))
    # a layer's name is one the benchmark already has
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] not in NEW}
    assert {by_name[n]["layer"] for n in NEW} <= layers
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, TRAFFIC)
    assert len(cell["why"]) <= 200
    for said in ("768", "384", "128 slots", "16 rows an expert",
                 "14 of 24"):
        assert said in cell["why"], said
    # one pair of configuration and traffic a cell; a quarter of the cells
    # at most on four chips, and those that were
    pairs = [(c["config"], c["traffic"]) for c in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [c["name"] for c in manifest["workloads"] if c["chips"] == 4]
    assert four == ["opt-1.3b.zero3-dp4"]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    # every configuration is run by some cell
    assert {c["name"] for c in manifest["configs"]} == {
        c["config"] for c in manifest["workloads"]}


@pytest.mark.parametrize("rule", ["test_keys_names_units",
                                  "test_moves_and_coverage",
                                  "test_files_exist",
                                  "test_config_files_agree_with_what_is_run"])
def test_the_manifest_rules_hold_with_one_more_cell(manifest, rule):
    from tests.benchmark import test_manifest

    getattr(test_manifest, rule)(manifest)


# ---- what pinned_thirteenth.py sets aside, every assertion but the pins

def test_the_ninth_configuration_is_still_the_catalogs_with_one_key_cut(
        manifest):
    """``test_delta_cell.py::test_the_configuration_is_the_catalogs_with_one_
    key_cut`` but its last line's count and position (nine configurations,
    this one last): the file against the catalog row, what it says it
    assumed and how it was cut, and its entry."""
    from tests.benchmark import test_delta_cell as D

    body = D._body()
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["published"] == {"num_hidden_layers": 32}
    assert body["num_hidden_layers"] == 16
    for key, value in D.PUBLISHED.items():
        if key == "layer_types":        # the first sixteen: four whole periods
            assert body[key] == value[:16]
        elif key not in body["reduced"]:
            assert body[key] == value, key
    assert (body["hidden_size"], body["num_attention_heads"],
            body["intermediate_size"], body["linear_key_head_dim"],
            body["linear_value_head_dim"], body["linear_conv_kernel_dim"],
            body["vocab_size"]) == (3840, 30, 11008, 96, 192, 4, 100352)
    assert body["source"] == ("https://huggingface.co/allenai/"
                              "Olmo-Hybrid-7B/blob/main/config.json")
    assert body["reference"] == "benchmark.lib.reference_olmo_hybrid"
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
    entry = next(c for c in manifest["configs"] if c["name"] == D.CONFIG)
    assert entry["reduced"] == body["reduced"]
    assert entry["source"] == body["source"]
    assert entry["file"] == f"benchmark/configs/{D.CONFIG}.json"


def test_the_twelfth_cell_still_reports_what_issue_51_listed(manifest):
    """``test_delta_cell.py::test_the_cell_reports_what_issue_51_lists`` but
    its counts and positions: what the cell reports, its four entries as
    they were, standing together in the issue's order, and the cell as it
    was."""
    from tests.benchmark import test_delta_cell as D

    reported = {m["name"] for m in manifest["per_layer"]
                if OLMO in m.get("workloads", [])}
    assert reported == set(D.NEW) | D.OLDER | D.PR49
    for m in manifest["per_layer"]:
        if m["name"].startswith(("moe_", "ssm_", "kv_window_", "loop_",
                                 "mla_", "hybrid_", "conv_")) or m[
                "name"] in ("kv_gather_useful_share.capacity",
                            "state_layers_traffic_share",
                            "prefill_device_share",
                            "window_recomputed_token_share"):
            assert OLMO not in m.get("workloads", []), m["name"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, source in (
            ("delta_decode_roofline", "paged forward", "device_trace"),
            ("delta_prefill_roofline", "serving executor", "device_trace"),
            ("delta_step_roofline", "paged forward", "device_trace"),
            ("delta_state_traffic_share", "paged forward",
             "program_counter")):
        m = by_name[name]
        assert m["workloads"] == [OLMO]
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == (layer, source, "serve_tokens_per_s", "%",
                                 "higher")
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(D.NEW[0])
    assert names[at:at + len(D.NEW)] == D.NEW
    cell = next(c for c in manifest["workloads"] if c["name"] == OLMO)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, D.CONFIG, "thinkrollout-backlog")
    assert len(cell["why"]) <= 200
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1
    for said in ("512", "768", "32 slots", "12 of 16", "16 of 32"):
        assert said in cell["why"], said
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert OLMO in e2e["serve_tokens_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    entry = next(c for c in manifest["configs"] if c["name"] == D.CONFIG)
    assert entry["file"] == f"benchmark/configs/{D.CONFIG}.json"


def test_the_seven_of_pr_49_still_stand_together(manifest):
    """``test_delta_cell.py::test_the_seven_of_pr_49_stand_together_behind_
    everything_it_found`` but its positions: every serving cell and every
    backlog cell, in the manifest's order, under the seven; the seven
    together, in the table's order, behind the 55 entries PR 49 found."""
    from tests.benchmark import test_window_account as table

    kinds = _kinds(manifest)
    serving = [c for c, k in kinds.items() if k.startswith("serve")]
    backlog = [c for c, k in kinds.items() if k == "serve-backlog"]
    assert len(serving) >= 9 and len(backlog) >= 7
    assert CELL in backlog and OLMO in backlog
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
    assert at == 55         # the 55 entries PR 49 found, untouched in order


# ------------------------------------ conv_moe_work and the three readers

def test_conv_moe_work_arithmetic():
    from benchmark.lib import conv_moe_work as W
    from benchmark.lib import delta_work, ssm_moe_work
    from deepspeed_tpu.models import get_config

    cfg = _cfg()
    assert W.applies(cfg) and W.layer_counts(cfg) == (11, 3, 2, 12)
    # its readers stay off the other models, and theirs off this one
    for other in (get_config("falcon-h1-34b", num_layers=5),
                  get_config("granite-4.0-h-small", num_layers=10),
                  get_config("olmo-hybrid-7b", num_layers=16),
                  get_config("olmoe-1b-7b"), get_config("opt-1.3b")):
        assert not W.applies(other)
    assert not ssm_moe_work.applies(cfg) and not delta_work.applies(cfg)
    assert W.conv_matmul_params(cfg) == 2048 * 6144 + 2048 * 2048
    assert W.conv_params(cfg) == 16_783_360
    assert W.attention_matmul_params(cfg) == 10_485_760
    assert W.attention_params(cfg) == 10_485_888
    assert W.dense_mlp_params(cfg) == 44_040_192
    assert W.expert_params(cfg) == 11_010_048
    assert W.router_params(cfg) == 65_568
    assert W.head_params(cfg) == 65_536 * 2048 + 2048
    assert W.held_params(cfg) == cfg.param_count == 4_667_077_376
    assert W.held_params(get_config("lfm2-8b-a1b")) == 8_339_930_560
    assert W.dense_params(cfg) == 4_667_077_376 - 12 * 32 * 11_010_048
    assert W.tail_bytes(cfg) == 8192 and 11 * W.tail_bytes(cfg) == 90_112
    assert W.kv_row_bytes(cfg) == 2048 and 3 * W.kv_row_bytes(cfg) == 6144
    # a full tick: 128 slots at ~1,000 rows each, 31.4 of 32 experts touched
    # a layer: 8.30 GB of experts, 0.88 GB of everything else's weights
    # (operators and dense layers 0.61, head 0.27), 0.79 GB of K/V, 23 MB of
    # tails: 9.99 GB, 12.2 ms at 819 GB/s, 83% of it the experts
    work = W.decode_tick_work(cfg, 12 * 31.4, 128, 128 * 1000 * 3)
    assert round(work["expert_bytes"] / 1e9, 2) == 8.30
    assert round(work["dense_bytes"] / 1e9, 2) == 0.88
    assert round(2 * (W.head_params(cfg)) / 1e9, 2) == 0.27
    assert round(2 * (11 * W.conv_params(cfg) + 3 * W.attention_params(cfg)
                      + 2 * W.dense_mlp_params(cfg)) / 1e9, 2) == 0.61
    assert work["kv_bytes"] == 128 * 1000 * 6144
    assert work["tail_bytes"] == 2 * 128 * 90_112 == 23_068_672
    total = sum(work.values())
    assert round(total / 1e9, 2) == 9.99
    assert round(total / 819e9 * 1e3, 1) == 12.2
    assert round(100 * work["expert_bytes"] / total) == 83
    # the operator's share of a tick's bytes: its weights and the tails
    operator = 2 * 11 * W.conv_params(cfg) + work["tail_bytes"]
    assert 3.5 < 100 * operator / total < 4.5
    # one token: two operations a matmul parameter and a pair, the head once
    one = W.prefill_flops(cfg, 1, 12 * 4)
    matmul = (11 * (2048 * 6144 + 2048 * 2048) + 3 * 10_485_760
              + 2 * 44_040_192 + 12 * 2048 * 32)
    assert one == pytest.approx(
        2 * matmul + 2 * 48 * 11_010_048 + 2 * 65_536 * 2048
        + 3 * 4 * 32 * 64 + 11 * 8 * 2048)
    # a median prompt of 768: ~1.3 TFLOP, 6.6 ms at the chip's peak, the
    # experts 63% of it, attention's triangle 0.6%
    prompt = W.prefill_flops(cfg, 768, 768 * 48)
    assert 1.25e12 < prompt < 1.35e12
    assert 0.6 < 2 * 768 * 48 * 11_010_048 / prompt < 0.66
    assert 768 * 769 / 2 * 3 * W.attention_ops_per_row(cfg) < 0.01 * prompt


def _record():
    cfg = _cfg()
    tick = dict(live_rows=120_000, state_slots=128, state_layers=11,
                kv_layers=3, kv_live_rows=3 * 120_128, state_bytes=1,
                state_passes=1, moe_rows=128 * 48, moe_live_rows=128 * 48,
                moe_experts_touched=377, moe_experts_held=384)
    spans = [
        _span("serve.decode", 1.0, **tick),
        _span("serve.decode", 1.1, **dict(tick, moe_experts_touched=375)),
        _span("serve.decode", 9.0, **dict(tick, state_slots=2,
                                          moe_rows=96)),        # the drain
        _span("serve.prefill", 1.2, tokens=700, bucket=1024, state_reset=1,
              moe_rows=700 * 48, pairs_held=700 * 48),
        _span("serve.prefill", 1.3, tokens=1500, bucket=2048, state_reset=1,
              moe_rows=1500 * 48, pairs_held=1500 * 48),
    ]
    host = [[10, 30, "serve.decode"], [100, 30, "serve.decode"],
            [200, 400, "serve.prefill"], [700, 200, "serve.prefill"]]
    modules = [[12, 19_000_000, "jit_serve_decode"],
               [102, 21_000_000, "jit_serve_decode"],
               [210, 40_000_000, "jit_serve_prefill_1024"],
               [710, 75_000_000, "jit_serve_prefill_2048"]]
    trace = {"modules": modules, "host": host, "busy_s": 0.16,
             "per_op_s": {}}
    return {"serve": {"cfg": cfg, "t_end": 5.0}, "spans": spans,
            "trace": trace, "device": {"kind": "TPU v5 lite"}}


def test_the_three_readers_on_a_hand_built_record():
    from benchmark.lib import conv_moe_work as W

    rec = _record()
    cfg = rec["serve"]["cfg"]
    mean = W.decode_tick_work(cfg, 376, 128, 3 * 120_128)
    assert _read("conv_moe_decode_roofline", rec) == pytest.approx(
        100 * (sum(mean.values()) / 819e9) / 20e-3)
    ops = (W.prefill_flops(cfg, 700, 700 * 48)
           + W.prefill_flops(cfg, 1500, 1500 * 48))
    assert _read("conv_moe_prefill_roofline", rec) == pytest.approx(
        100 * (ops / 197e12) / 0.115)
    # the drain's tick left out: 6,144 rows over 384 experts, twice
    assert _read("moe_rows_an_expert", rec) == pytest.approx(16.0)
    for name in NEW[:2]:
        assert 0 < _read(name, rec) <= 100, name


@pytest.mark.parametrize("name", NEW)
def test_the_readers_read_nothing_from_a_record_without_the_attrs(name):
    """Another model's configuration has no "conv" layer (the parent's
    programs: every cell the benchmark had); spans without the counters; no
    trace, no device metric."""
    from deepspeed_tpu.models import get_config

    rec = _record()
    if name != "moe_rows_an_expert":        # any model's expert counters
        for other in ("falcon-h1-34b", "granite-4.0-h-small", "opt-1.3b"):
            cfg = get_config(other, num_layers=5)
            assert _read(name, dict(rec, serve={"cfg": cfg})) is None
        assert _read(name, dict(rec, serve={})) is None
        assert _read(name, dict(rec, trace=None)) is None
    old = [_span(s.name, s.t0, **{k: v for k, v in s.attrs.items()
                                  if k in ("live_rows", "tokens", "bucket")})
           for s in rec["spans"]]
    assert _read(name, dict(rec, spans=old)) is None
    assert _read(name, dict(rec, spans=[])) is None
    if name == "conv_moe_prefill_roofline":
        no_prefill = dict(rec["trace"], modules=rec["trace"]["modules"][:2],
                          host=rec["trace"]["host"][:2])
        assert _read(name, dict(rec, trace=no_prefill)) is None


# ------------------------------------------------------------ end to end

def test_rehearse_the_cell(capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 54),
                             "--seconds", "1.5", "--trace", "1", "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] > 0 and res["failed"] == 0
    # every reader that needs no device trace is a number
    assert {"moe_rows_an_expert", "moe_live_row_share",
            "kv_gather_useful_share.capacity", "slots_active_mean",
            "window_compiles.serve", "prefill_pad_share",
            "tick_host_ms_p50.capacity", "admit_host_ms_p50",
            "host_busy_share", "window_tokens_per_tick",
            "window_decode_time_share", "window_prefill_time_share",
            "window_tick_ms_mean"} <= set(res["metric_names"])
    # no device metric from a CPU run, and none of another model's readers
    assert not {n for n in res["metric_names"] if "roofline" in n}
    assert not {n for n in res["metric_names"]
                if n.startswith(("ssm_", "delta_", "state_layers"))}
    checks = _last(out, "checks")
    assert checks["logits_match_reference"]
    assert checks["layers_match_reference"] and checks["pages_balanced"]
    layers = [json.loads(ln[5:]) for ln in out.splitlines()
              if ln.startswith("note ") and "layer_checks" in ln][-1]
    assert {"conv_operator", "attention_operator", "dense_mlp",
            "expert_layer", "tail_after_prefill", "tail_after_decode"} <= set(
        layers["layer_checks"])
