"""The cell of ISSUE 58 (``trinity-large-ep16-d8.mixedctx-backlog``): the
configuration's file against the catalog row and the four keys it cut, its
bytes reckoned again, ``lib/gated_swa_work.py``'s arithmetic by hand, the
three readers on hand-built records, the manifest's rules READ AS RULES with
one more cell (no count of cells or configurations, no "is last"), every
other assertion of the tests that ``pinned_fourteenth.py`` sets aside, and
the cell end to end at a tiny size on the CPU (``--rehearse --trace 1``)."""
import json
import os
import types

import pytest

from benchmark import run as bench_run
from tests.benchmark.test_room import room  # noqa: F401  (the fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "trinity-large-ep16-d8"
TRAFFIC = "mixedctx-backlog"
CELL = CONFIG + "." + TRAFFIC
NEW = ["gated_swa_decode_roofline", "gated_swa_prefill_roofline",
       "kv_past_window_slot_share"]
# readers of another model's cell that this one joins because they return a
# number for it (one reader a quantity, no second name): the two kinds of
# K/V rows, the held share's two, the experts' matmuls in the tick
JOINED = {"kv_window_rows_share", "kv_full_read_useful_share",
          "moe_local_pair_share", "moe_held_touched_share",
          "moe_expert_matmul_share"}
HELD = ("moe_local_pair_share", "moe_held_touched_share")
# how the sources of the configurations that hold a share of their routed
# experts spell the key that counts them (``reduced`` names the source's
# own keys): ``mimo_v2`` and ``deepseek_v3`` one way, ``afmoe`` the other
EXPERT_COUNT_KEYS = ("n_routed_experts", "num_experts")


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
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 15,
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
    "model_type": "afmoe", "moe_intermediate_size": 3072, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
CUT = {"num_hidden_layers": 8, "num_dense_layers": 1, "num_experts": 16,
       "vocab_size": 25024}


def test_the_configuration_is_the_catalogs_row_with_the_four_keys_cut(
        manifest):
    body = _body()
    assert len(PUBLISHED["layer_types"]) == 60
    assert body["reduced"] == list(CUT)
    assert body["published"] == {k: PUBLISHED[k] for k in CUT}
    for key, value in PUBLISHED.items():
        assert body[key] == CUT.get(key, value), key   # the pattern whole
    # no width is cut, nor the experts a token takes, nor the window
    assert not {"hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_tok", "sliding_window",
                "num_attention_heads", "num_key_value_heads"} & set(
        body["reduced"])
    # the floors: two whole periods behind the one dense layer, 16 >= 8
    # routed experts, an eighth of the vocabulary
    assert body["num_hidden_layers"] - body["num_dense_layers"] >= 4
    assert body["num_experts"] >= 8
    assert body["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert body["source"] == ("https://huggingface.co/arcee-ai/"
                              "Trinity-Large-Preview/blob/main/config.json")
    assert body["reference"] == "benchmark.lib.reference_afmoe"
    for said in ("entries 5-12", "experts 0-15", "25,024 is a slice",
                 "post_attention_layernorm on attention's OUTPUT",
                 "INITIALISATION", "sigmoid(g)", "NO position",
                 "sqrt(hidden_size)", "sum + 1e-20", "route_scale 2.448",
                 "load_balance_coeff is training's", "bfloat16",
                 "16 quantiles"):
        assert any(said in a for a in body["assumed"]), said
    for said in ("128 v5e chips", "8 pipeline stages of 16", "16 a chip",
                 "25,024 of 200,192", "layer 5", "layers 6-12",
                 "two whole periods", "62,914,816", "113,246,208",
                 "28,311,552", "545,010,176", "4,144,995,072", "8.29 GB",
                 "4,096 B a token a layer", "1,921 pages", "793 pages",
                 "33 pages a slot", "2.01 GB", "2.49 GB", "12.8 GB",
                 "without its exchange", "25.7 GB"):
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
                                                  layer_plan,
                                                  window_ring_pages)

    body, cfg = _body(), _cfg()
    kinds = [{"sliding_attention": "window", "full_attention": "full"}[t]
             for t in body["layer_types"][5:13]]
    assert kinds == ["window", "window", "full", "window", "window", "window",
                     "full", "window"]
    assert cfg == get_config(
        "trinity-large-preview", num_layers=8, layer_pattern=kinds,
        dense_layers=1, moe_experts_held=16, moe_expert_first=0,
        vocab_size=25024)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
            cfg.dims_per_head, cfg.norm_eps, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.vocab_size, cfg.max_seq_len,
            cfg.rope_theta, cfg.window_size, cfg.dense_layers) == (
        body["num_hidden_layers"], body["hidden_size"],
        body["num_attention_heads"], body["num_key_value_heads"],
        body["head_dim"], body["rms_norm_eps"], body["intermediate_size"],
        body["moe_intermediate_size"], body["vocab_size"],
        body["max_position_embeddings"], body["rope_theta"],
        body["sliding_window"], body["num_dense_layers"])
    # the router keeps its published width and its experts a token
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.moe_expert_first,
            cfg.moe_top_k, cfg.moe_score_func, cfg.moe_norm_topk_prob,
            cfg.moe_routed_scale, cfg.moe_shared_experts) == (
        body["published"]["num_experts"], body["num_experts"], 0,
        body["num_experts_per_tok"], body["score_func"], body["route_norm"],
        body["route_scale"], body["num_shared_experts"])
    assert cfg.embed_multiplier == pytest.approx(body["hidden_size"] ** 0.5)
    assert (cfg.position, cfg.window_position, cfg.qk_norm,
            cfg.attn_output_gate, cfg.sandwich_norm,
            cfg.tie_embeddings) == ("none", "rope", "head", True, True,
                                    body["tie_word_embeddings"])
    assert [k for _, _, k, _ in layer_plan(cfg)] == kinds
    assert [d for _, _, _, d in layer_plan(cfg)] == [True] + [False] * 7
    assert {g: n for g, (_, n) in layer_groups(cfg).items()} == {
        "window_dense": 1, "window_moe": 5, "full_moe": 2}
    assert cache_layers(cfg) == (8, 0)
    # the file's arithmetic, reckoned again
    d = 3072
    attn = 3 * d * 6144 + 2 * d * 1024 + 2 * 128
    dense, expert, router = 3 * d * 12288, 3 * d * d, d * 256 + 256
    assert (attn, dense, expert, router) == (
        62_914_816, 113_246_208, 28_311_552, 786_688)
    dense_layer = attn + 4 * d + dense
    moe_layer = attn + 4 * d + 17 * expert + router
    assert (dense_layer, moe_layer) == (176_173_312, 545_010_176)
    total = dense_layer + 7 * moe_layer + 2 * 25024 * d + d
    assert cfg.param_count == total == 4_144_995_072
    assert round(total * 2 / 1e9, 2) == 8.29
    traffic = _json("traffic", TRAFFIC + ".json")["engine"]
    slots, per_slot = traffic["b_slots"], traffic["max_model_len"] // 128
    ring = window_ring_pages(cfg.window_size, 128)
    token = 2 * 8 * 128 * 2
    assert (ring, token) == (33, 4096)
    if slots == 24:     # the deployment's paragraph is the 24-slot cell's
        assert (1 + slots * per_slot, 1 + slots * ring) == (1921, 793)
        full, rings = 1921 * 128 * token * 2, 793 * 128 * token * 6
        assert (round(full / 1e9, 2), round(rings / 1e9, 2)) == (2.01, 2.49)
        assert round((total * 2 + full + rings) / 1e9, 1) == 12.8
    # the rehearsal: all three groups, the window a ring of three pages
    small = system.transformer_config(body, rehearse=True)
    assert {g: n for g, (_, n) in layer_groups(small).items()} == {
        "window_dense": 1, "window_moe": 5, "full_moe": 2}
    assert small.attn_output_gate and small.window_position == "rope"
    assert window_ring_pages(small.window_size, 16) == 3


def test_the_traffic_is_what_issue_58_names():
    traffic = _json("traffic", TRAFFIC + ".json")
    assert traffic["kind"] == "serve-backlog"
    geo = traffic["engine"]
    assert (geo["page_size"], geo["max_model_len"]) == (128, 10240)
    assert 16 <= geo["b_slots"] <= 24
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 3072, "sigma": 0.9, "min": 256,
        "max": 8192}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256,
        "max": 2048}
    # a slot holds the longest prompt and the longest answer
    assert 8192 + 2048 <= geo["max_model_len"]
    par = traffic["parity"]
    assert (par["prompt"], par["decode"]) == (4500, 16)
    assert par["prompt"] > 4096 and par["prompt"] % 128
    reasoning = _json("traffic", "reasoning-backlog.json")
    assert (traffic["drain_seconds"], traffic["trace_ticks"]) == (
        reasoning["drain_seconds"], reasoning["trace_ticks"])
    others = {_json("traffic", f)["size_seed"]
              for f in os.listdir(os.path.join(ROOT, "benchmark", "traffic"))
              if f != TRAFFIC + ".json"
              and "size_seed" in _json("traffic", f)}
    assert traffic["size_seed"] not in others
    assert "sized on the chip" in traffic["notes"]
    # the draw: six buckets, and slots on both sides of the window
    import numpy as np

    from benchmark.lib import arrivals

    sizes = np.random.default_rng(traffic["size_seed"])
    prompts = arrivals.draw_lengths(traffic["prompt_tokens"],
                                    traffic["n_requests"], sizes)
    buckets = {int(2 ** np.ceil(np.log2(p))) for p in prompts}
    assert buckets == {256, 512, 1024, 2048, 4096, 8192}
    under = float((prompts <= 4096).mean())
    assert 0.45 < under < 0.75
    small = traffic["rehearse"]
    assert small["engine"]["b_slots"] >= 3
    assert small["parity"]["prompt"] > 2 * 32    # past the rehearsal's window


# ------------------------------------------------- the manifest, as rules

def test_the_cell_is_listed_under_every_reader_a_backlog_cell_lists(manifest):
    """Whatever every OTHER serve-backlog cell reports, this one reports; so
    with ``serve_tokens_per_s``; and what it reports beyond that is its own
    three and the readers it joined."""
    kinds = _kinds(manifest)
    backlog = [c for c, k in kinds.items() if k == "serve-backlog"]
    assert CELL in backlog
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
    # what reads another model's widths is not joined: MiMo's roofline
    # leaves the gate, two of the norms and the shared expert out of a
    # tick's bytes
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("hybrid_decode_roofline", "moe_decode_roofline",
                 "moe_experts_touched_share", "mla_decode_roofline",
                 "kv_gather_useful_share.capacity", "conv_moe_decode_roofline",
                 "ssm_moe_decode_roofline", "delta_decode_roofline"):
        assert CELL not in by_name[name]["workloads"], name


def test_the_new_entries_and_the_cell_are_what_issue_58_names(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, source in (
            ("gated_swa_decode_roofline", "paged forward", "device_trace"),
            ("gated_swa_prefill_roofline", "serving executor",
             "device_trace"),
            ("kv_past_window_slot_share", "paged forward",
             "program_counter")):
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == (layer, source, "serve_tokens_per_s", "%",
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
    slots = _json("traffic", TRAFFIC + ".json")["engine"]["b_slots"]
    for said in ("3,072", "1,024", f"{slots} slots", "past the window",
                 "a stage brings 6", "16x", "8 of 60"):
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


# ---- what pinned_fourteenth.py sets aside, every assertion but the pin

def held_share_rule(manifest, root=None):
    """``test_hybrid_cell.py::test_the_hybrid_metrics_list_the_new_cell_
    alone`` with the key's spellings read from ``EXPERT_COUNT_KEYS``: the
    two held-share readers are reported by exactly the serving cells whose
    configuration lists its source's count of routed experts under
    ``reduced``, each of which the system is told holds a share; the two
    K/V readers by cells of models with two kinds of layer alone; MiMo's
    roofline by MiMo's cell."""
    from benchmark.lib import system
    from deepspeed_tpu.models.transformer import is_hybrid
    from tests.benchmark import test_hybrid_cell as H

    root = root or H.ROOT
    configs = {c["name"]: c for c in manifest["configs"]}
    config_of = {c["name"]: configs[c["config"]]
                 for c in manifest["workloads"]}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    (serving,) = [set(m["workloads"]) for m in manifest["end_to_end"]
                  if m["name"] == "serve_tokens_per_s"]
    held = {cell for cell in serving
            if set(EXPERT_COUNT_KEYS) & set(config_of[cell]["reduced"])}

    def told(cell):
        with open(os.path.join(root, config_of[cell]["file"])) as f:
            return system.transformer_config(json.load(f), rehearse=False)

    for name in HELD:
        assert set(by_name[name]["workloads"]) == held, name
    for cell in held:
        assert told(cell).moe_experts_held, cell
    for name in ("kv_window_rows_share", "kv_full_read_useful_share",
                 "hybrid_decode_roofline"):
        assert H.CELL in by_name[name]["workloads"]
        for cell in by_name[name]["workloads"]:
            assert is_hybrid(told(cell)), (name, cell)
    for name in H.HYBRID | {"hybrid_decode_roofline"}:
        assert by_name[name]["moves"] == "serve_tokens_per_s"


def test_the_held_share_readers_list_the_cells_that_hold_a_share(manifest):
    held_share_rule(manifest)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in HELD:
        assert CELL in by_name[name]["workloads"]
        assert "mimo-v2.5-ep16-d7.reasoning-backlog" in by_name[name][
            "workloads"]
    # and the rule bites: a held cell left out of one of the two fails it
    import copy

    short = copy.deepcopy(manifest)
    next(m for m in short["per_layer"]
         if m["name"] == HELD[1])["workloads"].remove(CELL)
    with pytest.raises(AssertionError):
        held_share_rule(short)


@pytest.fixture
def rules(room, monkeypatch, tmp_path):  # noqa: F811
    """``test_room._manifest_rules`` on the fixture's copy, the pinned rule
    replaced by :func:`held_share_rule` reading the same copy."""
    from tests.benchmark import test_hybrid_cell, test_room

    monkeypatch.setattr(
        test_hybrid_cell, "test_the_hybrid_metrics_list_the_new_cell_alone",
        lambda manifest: held_share_rule(manifest, str(tmp_path)))
    return room, test_room


@pytest.mark.parametrize("where", ["last", "before the gap readers"])
def test_a_seventh_serving_cell_is_still_entries_and_files_alone(rules,
                                                                 where):
    """``test_room.py::test_a_seventh_serving_cell_is_entries_and_files_
    alone``, line for line, with the held-share rule as above."""
    from tests.benchmark import test_gap_anatomy

    room, test_room = rules
    manifest = room(where)
    test_room._manifest_rules(manifest)
    listed = {m["name"] for m in manifest["per_layer"]
              if test_room.CELL in m.get("workloads", [])}
    assert listed >= set(test_gap_anatomy.NAMES) | set(test_room.HELD) | {
        "seventh_state_share", "seventh_decode_roofline"}


def test_this_manifest_still_passes_in_the_copy(rules):
    """``test_room.py::test_the_parents_manifest_passes_in_the_copy``."""
    _, test_room = rules
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        test_room._manifest_rules(json.load(f))


# ----------------------------------- gated_swa_work and the three readers

def test_gated_swa_work_arithmetic():
    from benchmark.lib import conv_moe_work, gated_swa_work as W
    from benchmark.lib import hybrid_work, ssm_moe_work
    from deepspeed_tpu.models import get_config

    cfg = _cfg()
    assert W.applies(cfg) and W.layer_counts(cfg) == (6, 2, 1, 7)
    # its readers stay off the other models, and theirs off this one
    for other in (get_config("mimo-v2.5", num_layers=7),
                  get_config("kanana-2-30b-a3b", num_layers=4),
                  get_config("granite-4.0-h-small", num_layers=10),
                  get_config("lfm2-8b-a1b", num_layers=14),
                  get_config("olmoe-1b-7b"), get_config("opt-1.3b")):
        assert not W.applies(other)
    assert not conv_moe_work.applies(cfg) and not ssm_moe_work.applies(cfg)
    assert W.attention_matmul_params(cfg) == 62_914_560
    assert W.attention_params(cfg) == 62_914_816
    assert W.norm_params(cfg) == 12_288
    assert W.dense_mlp_params(cfg) == 113_246_208
    assert W.expert_params(cfg) == W.shared_params(cfg) == 28_311_552
    assert W.router_params(cfg) == 786_688
    assert W.head_params(cfg) == 25_024 * 3072 + 3072
    # every parameter the chip holds is the system's own count (ISSUE 58's
    # 4,144,997,120 counts the QK-norm's 256 twice a layer)
    assert W.held_params(cfg) == cfg.param_count == 4_144_995_072
    whole = get_config("trinity-large-preview")
    assert W.held_params(whole) == whole.param_count
    assert round(whole.param_count / 1e9, 1) == 398.6     # "400B"
    assert W.streamed_params(cfg) == (4_144_995_072 - 25_024 * 3072
                                      - 7 * 16 * 28_311_552)
    # MiMo's yardstick would leave the gate, two norms and the shared
    # expert out of this model's tick: why the cell is not under its
    # roofline
    assert (2 * W.streamed_params(cfg)
            - hybrid_work.non_expert_weight_bytes(cfg)) == 2 * (
        8 * (3072 * 6144 + 2 * 128 + 2 * 3072) + 7 * 28_311_552)
    assert W.head_row_bytes(cfg) == 512
    # a full tick: 24 slots, 14 under the window at ~2,000 rows and 10 past
    # it at ~7,000; ~5 of 16 held experts touched a layer.  Weights outside
    # the routed experts 1.79 GB, experts 1.98 GB, K/V 2.58 GB of which the
    # six rings' 1.69: 6.35 GB, 7.8 ms at 819 GB/s
    rows_full = (14 * 2000 + 10 * 7000) * 2 * 8
    rows_window = (14 * 2000 + 10 * 4096) * 6 * 8
    work = W.decode_tick_work(cfg, 7 * 5, rows_full, rows_window)
    assert round(work["streamed_bytes"] / 1e9, 2) == 1.79
    assert round(work["expert_bytes"] / 1e9, 2) == 1.98
    assert round(work["kv_full_bytes"] / 1e9, 2) == 0.80
    assert round(work["kv_window_bytes"] / 1e9, 2) == 1.69
    total = sum(work.values())
    assert round(total / 1e9, 2) == 6.27
    assert round(total / 819e9 * 1e3, 1) == 7.7
    assert 0.66 < work["kv_window_bytes"] / (
        work["kv_window_bytes"] + work["kv_full_bytes"]) < 0.69
    # rows a prompt's queries see: the triangle, under a window the
    # triangle's first 4,096 rows and then the window a query
    assert W.visible_rows(3) == 6 and W.visible_rows(3, 4096) == 6
    assert W.visible_rows(4096, 4096) == W.visible_rows(4096)
    assert W.visible_rows(8192, 4096) == (4096 * 4097 / 2 + 4096 * 4096)
    assert W.visible_rows(8192, 4096) / W.visible_rows(8192) == (
        pytest.approx(0.75, abs=1e-3))
    # one token: two operations a matmul parameter and a pair, the head
    # once, one row seen a layer
    one = W.prefill_flops(cfg, 1, 7 * 4 / 16)
    matmul = (8 * 62_914_560 + 113_246_208
              + 7 * (3072 * 256 + 28_311_552))
    assert one == pytest.approx(
        2 * matmul + 2 * 1.75 * 28_311_552 + 2 * 25_024 * 3072
        + 8 * 4 * 48 * 128)
    # a prompt of 8,192 with a sixteenth of its pairs held: 19.6 TFLOP, 100
    # ms at the chip's peak; attention's products 27% of it, and a fifth
    # less than without the window (a quarter in each window layer)
    prompt = W.prefill_flops(cfg, 8192, 8192 * 7 * 4 / 16)
    assert 19.5e12 < prompt < 19.7e12
    products = W.attention_ops_per_row(cfg) * (
        2 * W.visible_rows(8192) + 6 * W.visible_rows(8192, 4096))
    assert 0.26 < products / prompt < 0.28
    unbounded = W.attention_ops_per_row(cfg) * 8 * W.visible_rows(8192)
    assert 0.18 < (unbounded - products) / unbounded < 0.20


def _record():
    cfg = _cfg()
    tick = dict(live_rows=98_000, gathered_rows=101_000,
                kv_rows_full=101_000 * 16, kv_live_rows_full=98_024 * 16,
                kv_rows_window=72_000 * 48, kv_live_rows_window=68_960 * 48,
                kv_slots_live=24, kv_slots_past_window=10,
                moe_rows=41, moe_live_rows=24 * 28, moe_pairs=24 * 28,
                moe_local_pairs=41, moe_experts_touched=34,
                moe_experts_held=112)
    spans = [
        _span("serve.decode", 1.0, **tick),
        _span("serve.decode", 1.1, **dict(tick, moe_experts_touched=36,
                                          kv_slots_past_window=8)),
        _span("serve.decode", 9.0, **dict(tick, kv_slots_live=2,
                                          kv_slots_past_window=2)),  # drain
        _span("serve.prefill", 1.2, tokens=700, bucket=1024,
              moe_rows=1200, pairs_held=1200, pairs_total=700 * 28),
        _span("serve.prefill", 1.3, tokens=6000, bucket=8192,
              moe_rows=10_400, pairs_held=10_400, pairs_total=6000 * 28),
    ]
    host = [[10, 30, "serve.decode"], [100, 30, "serve.decode"],
            [200, 400, "serve.prefill"], [700, 200, "serve.prefill"]]
    modules = [[12, 12_000_000, "jit_serve_decode"],
               [102, 14_000_000, "jit_serve_decode"],
               [210, 30_000_000, "jit_serve_prefill_1024"],
               [710, 270_000_000, "jit_serve_prefill_8192"]]
    trace = {"modules": modules, "host": host, "busy_s": 0.33,
             "per_op_s": {}}
    return {"serve": {"cfg": cfg, "t_end": 5.0}, "spans": spans,
            "trace": trace, "device": {"kind": "TPU v5 lite"}}


def test_the_three_readers_on_a_hand_built_record():
    from benchmark.lib import gated_swa_work as W

    rec = _record()
    cfg = rec["serve"]["cfg"]
    mean = W.decode_tick_work(cfg, 35, 98_024 * 16, 68_960 * 48)
    assert _read("gated_swa_decode_roofline", rec) == pytest.approx(
        100 * (sum(mean.values()) / 819e9) / 13e-3)
    ops = (W.prefill_flops(cfg, 700, 1200)
           + W.prefill_flops(cfg, 6000, 10_400))
    assert _read("gated_swa_prefill_roofline", rec) == pytest.approx(
        100 * (ops / 197e12) / 0.3)
    # the drain's tick left out: 10 + 8 of 2 x 24 slots
    assert _read("kv_past_window_slot_share", rec) == pytest.approx(37.5)
    for name in NEW:
        assert 0 < _read(name, rec) <= 100, name
    # the two kinds' readers it joins read the same spans
    assert _read("kv_window_rows_share", rec) == pytest.approx(
        100 * 72_000 * 48 / (72_000 * 48 + 101_000 * 16))
    assert _read("moe_held_touched_share", rec) == pytest.approx(
        100 * 35 / 112)


@pytest.mark.parametrize("name", NEW)
def test_the_readers_read_nothing_from_a_record_without_the_attrs(name):
    """Another model's configuration has no gate (the parent's programs:
    every cell the benchmark had, MiMo's two kinds of layer among them);
    spans without the counters; no trace, no device metric."""
    from deepspeed_tpu.models import get_config

    rec = _record()
    if name != "kv_past_window_slot_share":     # its attrs are this PR's
        for other in ("mimo-v2.5", "granite-4.0-h-small", "opt-1.3b"):
            cfg = get_config(other, num_layers=5)
            assert _read(name, dict(rec, serve={"cfg": cfg})) is None
        assert _read(name, dict(rec, serve={})) is None
        assert _read(name, dict(rec, trace=None)) is None
    old = [_span(s.name, s.t0, **{k: v for k, v in s.attrs.items()
                                  if k in ("live_rows", "tokens", "bucket",
                                           "kv_rows_full", "kv_rows_window")})
           for s in rec["spans"]]
    assert _read(name, dict(rec, spans=old)) is None
    assert _read(name, dict(rec, spans=[])) is None
    if name == "gated_swa_prefill_roofline":
        no_prefill = dict(rec["trace"], modules=rec["trace"]["modules"][:2],
                          host=rec["trace"]["host"][:2])
        assert _read(name, dict(rec, trace=no_prefill)) is None


# ------------------------------------------------------------ end to end

def test_rehearse_the_cell(capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 58),
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
    assert {"kv_past_window_slot_share", "kv_window_rows_share",
            "kv_full_read_useful_share", "moe_local_pair_share",
            "moe_held_touched_share", "slots_active_mean",
            "window_compiles.serve", "prefill_pad_share",
            "tick_host_ms_p50.capacity", "admit_host_ms_p50",
            "host_busy_share", "window_tokens_per_tick",
            "window_decode_time_share", "window_prefill_time_share",
            "window_tick_ms_mean"} <= set(res["metric_names"])
    # no device metric from a CPU run, and none of another model's readers
    assert not {n for n in res["metric_names"] if "roofline" in n}
    assert not {n for n in res["metric_names"]
                if n.startswith(("ssm_", "delta_", "conv_", "mla_", "loop_"))}
    checks = _last(out, "checks")
    assert checks["logits_match_reference"]
    assert checks["layers_match_reference"] and checks["pages_balanced"]
    layers = [json.loads(ln[5:]) for ln in out.splitlines()
              if ln.startswith("note ") and "layer_checks" in ln][-1]
    assert set(layers["layer_checks"]) == {
        "window_attention", "window_tick", "full_attention", "full_tick",
        "expert_layer"}
