"""The cell of ISSUE 61 (``nemotron-3-super-ep4-d11.subagent-backlog``): the
configuration's file against the catalog row and what it says it cut,
``lib/latent_moe_work.py``'s arithmetic by hand, the three readers on
hand-built records, the cell and its readers by rules read from the files
(no count, no position), and the cell end to end at a tiny size on the CPU
(``--rehearse --trace 1``)."""
import json
import os
import types

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "nemotron-3-super-ep4-d11"
CELL = CONFIG + ".subagent-backlog"
NEW = {"latent_moe_decode_roofline", "latent_moe_prefill_roofline",
       "moe_latent_traffic_share"}
HELD = {"moe_local_pair_share", "moe_held_touched_share"}
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _body():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "subagent-backlog.json")) as f:
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
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 4096, "hybrid_override_pattern": PATTERN,
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def test_the_configuration_is_the_catalogs_with_three_keys_cut(manifest):
    body = _body()
    assert len(PATTERN) == 88
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert body["published"] == {key: PUBLISHED[key]
                                 for key in body["reduced"]}
    assert (body["num_hidden_layers"], body["n_routed_experts"],
            body["vocab_size"]) == (11, 128, 32768)
    for key, value in PUBLISHED.items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    assert body["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
        "BF16/blob/main/config.json")
    assert body["reference"] == "benchmark.lib.reference_nemotron_h"
    for said in ("ONE sublayer", "[z | xBC | dt]", "h // 16", "WITHIN each",
                 "NO position", "e_score_correction_bias", "1e-20",
                 "4,096 -> moe_latent_size 1,024", "relu(W1_e u)^2",
                 "LEFT OUT: the multi-token-prediction module",
                 "log U(1, 16)", "float32", "128 quantiles", "is a slice"):
        assert any(said in a for a in body["assumed"]), said
    for said in ("32 v5e chips", "8 pipeline stages of 4", "128 a chip",
                 "MEMEMEM*EME", "109,640,064", "35,655,680", "54,530,560",
                 "5,505,024", "759,173,632", "4,648,163,712", "9.30 GB",
                 "4,255,744 B", "1,024 B", "2.72 GB", "7,169 pages",
                 "0.94 GB", "12.96 GB of 16", "5.5 rows", "brings it 22",
                 "Training is left out"):
        assert said in body["deployment"], said
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == body["reduced"]
    assert entry["source"] == body["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_system_is_told_what_the_file_says():
    from benchmark.lib import system
    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models.mixers.ssm import ssm_in_width
    from deepspeed_tpu.models.transformer import cache_layers, layer_plan

    body, cfg = _body(), _cfg()
    assert cfg == get_config("nemotron-3-super-120b-a12b", num_layers=11,
                             moe_experts_held=128, moe_expert_first=0,
                             vocab_size=32768)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
            cfg.dims_per_head, cfg.norm_eps, cfg.vocab_size) == (
        body["num_hidden_layers"], body["hidden_size"],
        body["num_attention_heads"], body["num_key_value_heads"],
        body["head_dim"], body["norm_eps"], body["vocab_size"])
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (
        body["mamba_num_heads"], body["mamba_head_dim"],
        body["ssm_state_size"], body["n_groups"], body["conv_kernel"],
        body["chunk_size"])
    assert cfg.ssm_heads * cfg.ssm_head_dim == (
        body["expand"] * body["hidden_size"])
    assert ssm_in_width(cfg) == 18560
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.moe_top_k,
            cfg.moe_intermediate_size, cfg.moe_latent_size,
            cfg.moe_shared_experts * cfg.moe_intermediate_size,
            cfg.moe_routed_scale) == (
        512, body["n_routed_experts"], body["num_experts_per_tok"],
        body["moe_intermediate_size"], body["moe_latent_size"],
        body["moe_shared_expert_intermediate_size"],
        body["routed_scaling_factor"])
    assert (cfg.moe_score_func, cfg.moe_select_bias, cfg.moe_norm_topk_prob,
            cfg.moe_norm_topk_eps, cfg.moe_drop_tokens, cfg.activation) == (
        "sigmoid", True, body["norm_topk_prob"], 1e-20, False,
        body["mlp_hidden_act"])
    assert (cfg.position, cfg.tie_embeddings, cfg.one_sublayer) == (
        "none", body["tie_word_embeddings"], True)
    # the 11 layers run are the first 11 of the published 88 letters
    kinds = [{"M": "ssm", "E": "mlp", "*": "full"}[c]
             for c in body["hybrid_override_pattern"]]
    assert list(cfg.layer_pattern) == kinds
    assert [k for _, _, k, _ in layer_plan(cfg)] == kinds[:11]
    assert "".join(body["hybrid_override_pattern"][:11]) == "MEMEMEM*EME"
    assert cache_layers(cfg) == (1, 5)
    # the file's arithmetic: 9.30 GB of weights, 2.72 GB of state, 0.94 GB
    # of K/V in 7,169 pages
    assert cfg.param_count == 4_648_163_712
    t = _traffic()["engine"]
    slots, pages = t["b_slots"], 1 + t["b_slots"] * (
        t["max_model_len"] // t["page_size"])
    assert (slots, pages) == (128, 7169)
    assert round(slots * 5 * 4_255_744 / 1e9, 2) == 2.72
    assert round(pages * 128 * 1024 / 1e9, 2) == 0.94
    # the rehearsal: 11 layers of the same letters, 8 held of 32, 2 groups
    small = system.transformer_config(body, rehearse=True)
    assert [k for _, _, k, _ in layer_plan(small)] == kinds[:11]
    assert (small.num_experts, small.moe_experts_held, small.ssm_groups,
            small.moe_latent_size) == (32, 8, 2, 32)
    assert small.moe_latent_size < small.hidden_size


def test_the_traffic_is_what_issue_61_names():
    traffic = _traffic()
    assert traffic["kind"] == "serve-backlog"
    assert traffic["engine"] == {"b_slots": 128, "page_size": 128,
                                 "max_model_len": 7168}
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64,
        "max": 4096}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256,
        "max": 3072}
    # a slot holds the longest prompt and the longest answer
    assert 4096 + 3072 <= 7168
    assert traffic["parity"] == {"prompt": 3000, "decode": 16}
    # across a scan chunk and a page (128) and ending inside both
    assert traffic["parity"]["prompt"] % 128
    assert traffic["drain_seconds"] == 90 and traffic["trace_ticks"] == 60
    assert "sized on the chip" in traffic["notes"]
    assert traffic["rehearse"]["parity"]["prompt"] % 8
    # sizes of its own: no other mix draws from this seed
    others = []
    for name in os.listdir(os.path.join(ROOT, "benchmark", "traffic")):
        if name != "subagent-backlog.json":
            with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
                others.append(json.load(f).get("size_seed"))
    assert traffic["size_seed"] not in others


def test_the_cell_and_its_readers_by_rules_read_from_the_files(manifest):
    """No count and no position: what every serving backlog cell of a held
    share reports is read from the cells that are there, what this one adds
    from its own lists."""
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "subagent-backlog")
    for said in ("128 slots", "5.5 rows", "22", "11 of 88"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    # the other backlog cells, by their traffic files' kind
    backlog = set()
    for c in manifest["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               c["traffic"] + ".json")) as f:
            if json.load(f)["kind"] == "serve-backlog":
                backlog.add(c["name"])
    assert CELL in backlog
    reported = {m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", [])}
    every = {m["name"] for m in manifest["per_layer"]
             if set(m.get("workloads", ())) >= backlog - {CELL}}
    assert every <= reported
    # a held share (n_routed_experts under reduced) lists the two readers
    assert "n_routed_experts" in _body()["reduced"] and HELD <= reported
    assert reported == every | HELD | NEW | {"moe_rows_an_expert",
                                             "moe_expert_matmul_share"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, source in (
            ("latent_moe_decode_roofline", "paged forward", "device_trace"),
            ("latent_moe_prefill_roofline", "serving executor",
             "device_trace"),
            ("moe_latent_traffic_share", "expert layer", "program_counter")):
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == (layer, source, "serve_tokens_per_s", "%",
                                 "higher")
    # readers that reckon another model's layers keep their lists
    for name in ("state_layers_traffic_share", "ssm_moe_decode_roofline",
                 "ssm_moe_prefill_roofline", "ssm_state_traffic_share",
                 "moe_live_row_share", "kv_gather_useful_share.capacity"):
        assert CELL not in by_name[name]["workloads"], name
    # one cell in four may take four chips, and one does
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1


def test_the_thirteenth_cells_entries_still_stand(manifest):
    """``test_conv_moe_cell.py::test_the_new_entries_and_the_cell_are_what_
    issue_54_names`` but the one line ``pinned_fifteenth.py`` sets aside
    (``moe_rows_an_expert`` listing the LFM2 cell ALONE): its three entries,
    their layers, its cell, and the rules on pairs, four-chip cells and
    configurations that every cell runs."""
    from tests.benchmark import test_conv_moe_cell as C

    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, source, unit in (
            ("conv_moe_decode_roofline", "paged forward", "device_trace", "%"),
            ("conv_moe_prefill_roofline", "serving executor", "device_trace",
             "%"),
            ("moe_rows_an_expert", "expert layer", "program_counter",
             "rows")):
        m = by_name[name]
        # the cell that brought the reader stays first in its list
        assert m["workloads"][0] == C.CELL
        assert m["workloads"] in ([C.CELL], [C.CELL, CELL])
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == (layer, source, "serve_tokens_per_s", unit,
                                 "higher")
        assert os.path.isfile(bench_run.reader_path(name))
    assert by_name["moe_rows_an_expert"]["workloads"] == [C.CELL, CELL]
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] not in C.NEW}
    assert {by_name[n]["layer"] for n in C.NEW} <= layers
    cell = next(c for c in manifest["workloads"] if c["name"] == C.CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, C.CONFIG, C.TRAFFIC)
    for said in ("768", "384", "128 slots", "16 rows an expert",
                 "14 of 24"):
        assert said in cell["why"], said
    pairs = [(c["config"], c["traffic"]) for c in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [c["name"] for c in manifest["workloads"] if c["chips"] == 4]
    assert four == ["opt-1.3b.zero3-dp4"]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert {c["name"] for c in manifest["configs"]} == {
        c["config"] for c in manifest["workloads"]}


@pytest.mark.parametrize("rule", ["test_keys_names_units",
                                  "test_moves_and_coverage",
                                  "test_files_exist",
                                  "test_config_files_agree_with_what_is_run"])
def test_the_manifest_rules_hold_with_this_cell(manifest, rule):
    from tests.benchmark import test_manifest

    getattr(test_manifest, rule)(manifest)


# -------------------------------------- latent_moe_work and the three readers

def test_latent_moe_work_arithmetic():
    from benchmark.lib import latent_moe_work as W
    from benchmark.lib import ssm_work
    from deepspeed_tpu.models import get_config

    cfg = _cfg()
    assert W.applies(cfg)
    assert W.layer_counts(cfg) == {"ssm": 5, "mlp": 5, "full": 1}
    assert W.mixer_layer_params(cfg) == (
        4096 * 18560 + 8192 * 4096 + 10240 * 5 + 3 * 128 + 8192
        + 4096) == 109_640_064
    assert W.attention_layer_params(cfg) == (
        2 * 4096 * 4096 + 2 * 4096 * 256 + 4096) == 35_655_680
    assert W.expert_params(cfg) == 2 * 1024 * 2688 == 5_505_024
    assert W.expert_layer_dense_params(cfg) == (
        4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
        + 4096) == 54_530_560
    assert W.head_params(cfg) == 32768 * 4096 + 4096
    assert W.held_params(cfg) == cfg.param_count == 4_648_163_712
    whole = get_config("nemotron-3-super-120b-a12b")
    assert W.held_params(whole) == whole.param_count == 120_668_707_840
    assert round(W.held_params(whole) / 1e9, 2) == 120.67
    # 22 experts a token: 12.77 B parameters touched a token
    active = (W.held_params(whole)
              - 40 * (512 - 22) * W.expert_params(whole))
    assert round(active / 1e9, 2) == 12.77
    assert ssm_work.state_bytes(cfg) == 4_255_744
    assert ssm_work.kv_row_bytes(cfg) == 1_024
    # a tick of 128 live slots at 1,500 rows each that touches every held
    # expert: 7.6 GB of expert layers, 5.4 GB of state, 1.1 GB of mixers
    work = W.decode_tick_work(cfg, 640, 128, 128 * 1500)
    assert work["expert_layer_bytes"] == 2 * 5 * (54_530_560
                                                  + 128 * 5_505_024)
    assert round(work["expert_layer_bytes"] / 1e9, 1) == 7.6
    assert work["state_bytes"] == 2 * 128 * 5 * 4_255_744
    assert round(work["state_bytes"] / 1e9, 1) == 5.4
    assert round(work["mixer_weight_bytes"] / 1e9, 1) == 1.1
    assert work["kv_bytes"] == 128 * 1500 * 1024
    assert sum(work.values()) - work["state_bytes"] - work["kv_bytes"] == (
        2 * (W.held_params(cfg) - 32768 * 4096))    # all but the embedding
    assert round(sum(work.values()) / 819e9 * 1e3, 1) == 17.9
    # an expert no row reached is not streamed
    assert (work["expert_layer_bytes"] - W.decode_tick_work(
        cfg, 630, 128, 0)["expert_layer_bytes"]) == 10 * 2 * 5_505_024
    # one token that lands five of its 22 pairs a layer on held experts
    one = W.prefill_flops(cfg, 1, 25)
    dense = (5 * (4096 * 18560 + 8192 * 4096) + 2 * 4096 * 4096
             + 2 * 4096 * 256 + 5 * (4096 * 512 + 2 * 4096 * 1024
                                     + 2 * 4096 * 5376))
    assert one == pytest.approx(
        2 * dense + 2 * 25 * 5_505_024 + 2 * 32768 * 4096
        + 4 * 32 * 128 + 5 * (5 * 128 * 64 * 128 + 2 * 4 * 10240))
    prompt = W.prefill_flops(cfg, 512, 512 * 25)
    assert 1.0e12 < prompt < 1.2e12             # ~1.1 TFLOP a median prompt


def _record():
    cfg = _cfg()
    tick = dict(live_rows=190_000, state_slots=128, state_layers=5,
                kv_layers=1, kv_live_rows=190_128, state_bytes=1,
                experts_touched_held=636, pairs_held=3500, pairs_total=14080,
                moe_latent_rows=640, layers_by_kind="ssm:5,mlp:5,full:1")
    spans = [
        _span("serve.decode", 1.0, **tick),
        _span("serve.decode", 1.1, **dict(tick, experts_touched_held=640)),
        _span("serve.decode", 9.0, **dict(tick, state_slots=2)),    # drain
        _span("serve.prefill", 1.2, tokens=600, bucket=1024,
              scan_chunks=5, pairs_held=16_500, pairs_total=66_000,
              moe_latent_rows=3000),
        _span("serve.prefill", 1.3, tokens=250, bucket=256,
              scan_chunks=2, pairs_held=6_900, pairs_total=27_500,
              moe_latent_rows=1250),
    ]
    host = [[10, 30, "serve.decode"], [100, 30, "serve.decode"],
            [200, 400, "serve.prefill"], [700, 200, "serve.prefill"]]
    modules = [[12, 25_000_000, "jit_serve_decode"],
               [102, 27_000_000, "jit_serve_decode"],
               [210, 30_000_000, "jit_serve_prefill_1024"],
               [710, 12_000_000, "jit_serve_prefill_256"]]
    trace = {"modules": modules, "host": host, "busy_s": 0.3,
             "per_op_s": {}}
    return {"serve": {"cfg": cfg, "t_end": 5.0}, "spans": spans,
            "trace": trace, "device": {"kind": "TPU v5 lite"}}


def test_the_three_readers_on_a_hand_built_record():
    from benchmark.lib import latent_moe_work as W

    rec = _record()
    cfg = rec["serve"]["cfg"]
    w = [W.decode_tick_work(cfg, e, 128, 190_128) for e in (636, 640)]
    experts = sum(x["expert_layer_bytes"] for x in w)
    assert _read("moe_latent_traffic_share", rec) == pytest.approx(
        100 * experts / sum(sum(x.values()) for x in w))
    assert 49 < _read("moe_latent_traffic_share", rec) < 54
    mean = W.decode_tick_work(cfg, 638, 128, 190_128)
    assert _read("latent_moe_decode_roofline", rec) == pytest.approx(
        100 * (sum(mean.values()) / 819e9) / 26e-3)
    ops = W.prefill_flops(cfg, 600, 16_500) + W.prefill_flops(
        cfg, 250, 6_900)
    assert _read("latent_moe_prefill_roofline", rec) == pytest.approx(
        100 * (ops / 197e12) / 0.042)
    for name in NEW:
        assert 0 < _read(name, rec) <= 100, name


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_readers_read_nothing_from_a_program_without_the_counters(name):
    """The parent's spans of a state model with experts carry no
    ``moe_latent_rows``; another model's configuration has no latent and no
    one-sublayer layers; no trace, no device metric."""
    from deepspeed_tpu.models import get_config

    rec = _record()
    old = [_span(s.name, s.t0, **{k: v for k, v in s.attrs.items()
                                  if k not in ("moe_latent_rows",
                                               "layers_by_kind")})
           for s in rec["spans"]]
    assert _read(name, dict(rec, spans=old)) is None
    other = dict(rec, serve={"cfg": get_config("granite-4.0-h-small",
                                               num_layers=10)})
    assert _read(name, other) is None
    assert _read(name, dict(rec, spans=[])) is None
    if name != "moe_latent_traffic_share":
        assert _read(name, dict(rec, trace=None)) is None
    no_prefill = dict(rec["trace"], modules=rec["trace"]["modules"][:2],
                      host=rec["trace"]["host"][:2])
    if "prefill" in name:
        assert _read(name, dict(rec, trace=no_prefill)) is None


# ------------------------------------------------------------ end to end

def test_rehearse_the_cell(capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 61),
                             "--seconds", "1.5", "--trace", "1", "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"moe_latent_traffic_share", "moe_local_pair_share",
            "moe_held_touched_share", "moe_rows_an_expert",
            "slots_active_mean", "window_compiles.serve",
            "prefill_pad_share", "tick_host_ms_p50.capacity",
            "admit_host_ms_p50", "host_busy_share",
            "cache_misses"} <= set(res["metric_names"])
    # no device metric from a CPU run, and none of another model's readers
    assert not {n for n in res["metric_names"] if "roofline" in n}
    assert not {"state_layers_traffic_share", "ssm_state_traffic_share",
                "moe_live_row_share"} & set(res["metric_names"])
    checks = _last(out, "checks")
    assert checks["logits_match_reference"]
    assert checks["layers_match_reference"] and checks["pages_balanced"]
    layers = [json.loads(ln[5:]) for ln in out.splitlines()
              if ln.startswith("note ") and "layer_checks" in ln][-1]
    assert {"mixer_layer", "attention_layer", "expert_layer",
            "router_near_tie_share"} <= set(layers["layer_checks"])
