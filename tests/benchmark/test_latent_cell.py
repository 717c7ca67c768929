"""The cell of ISSUE 32 (``kanana-2-30b-a3b-ep8-d24.longctx-backlog``): the
configuration's file against the catalog's numbers and what it says it cut,
``lib/mla_work.py``'s arithmetic, the readers of a latent-attention model on
hand-built records (and on a program that has no such counters), and the
cell end to end at a tiny size on the CPU (``--rehearse --trace 1``)."""
import json
import os
import types

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "kanana-2-30b-a3b-ep8-d24.longctx-backlog"
NEW = {"kv_latent_traffic_share", "moe_local_pair_share",
       "moe_held_touched_share"}


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
        rc = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 32),
                             "--seconds", "1.5", "--trace", "1", "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] > 0 and res["failed"] == 0
    assert NEW | {"kv_gather_useful_share.capacity", "slots_active_mean",
                  "window_compiles.serve", "prefill_pad_share",
                  "tick_host_ms_p50.capacity", "admit_host_ms_p50",
                  "cache_misses"} <= set(res["metric_names"])
    # no device metric from a CPU run
    assert not {n for n in res["metric_names"] if "roofline" in n}
    checks = _last(out, "checks")
    assert checks["layers_match_reference"] and checks["pages_balanced"]


def test_the_cell_reports_what_issue_32_lists(manifest):
    reported = {m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", [])}
    # containment, not equality: a later PR lists the cell under a metric
    # of its own, or a second latent model under these readers, by adding
    # entries and files only
    assert reported >= NEW | {
        "mla_decode_roofline", "slots_active_mean", "prefill_ms_p50",
        "window_compiles.serve", "device_idle_share.serve",
        "peak_hbm_gb.serve", "host_bound_idle_share.serve",
        "admit_host_ms_p50", "prefill_pad_share", "decode_ms_p50.capacity",
        "tick_host_ms_p50.capacity", "kv_gather_useful_share.capacity",
        "moe_expert_matmul_share"}
    for m in manifest["per_layer"]:
        if m["name"] in NEW | {"mla_decode_roofline"}:
            assert CELL in m["workloads"]
            assert m["moves"] == "serve_tokens_per_s"
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longctx-backlog"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]


def test_the_configuration_is_the_catalogs_with_three_keys_cut():
    body = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "kanana-2-30b-a3b-ep8-d24.json")))
    published = {      # the catalog's ``config``, every number of it
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert body["published"] == {k: published[k] for k in body["reduced"]}
    assert (body["num_hidden_layers"], body["n_routed_experts"],
            body["vocab_size"]) == (24, 16, 16032)
    for key, value in published.items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    for said in ("2 pipeline stages of 8 chips", "5.39 GB", "7.25 GB",
                 "1,152 B", "1.5 rows an expert", "would bring 12"):
        assert said in body["deployment"], said
    assert len(body["assumed"]) >= 8
    from benchmark.lib import system

    cfg = system.transformer_config(body, rehearse=False)
    assert (cfg.kv_lora_rank, cfg.dims_per_head, cfg.rotary_dim,
            cfg.v_head_dim, cfg.num_heads) == (
        body["kv_lora_rank"], body["qk_head_dim"], body["qk_rope_head_dim"],
        body["v_head_dim"], body["num_attention_heads"])
    assert cfg.dims_per_head - cfg.rotary_dim == body["qk_nope_head_dim"]
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.moe_top_k,
            cfg.moe_shared_experts, cfg.moe_routed_scale, cfg.dense_layers,
            cfg.moe_intermediate_size) == (
        128, 16, body["num_experts_per_tok"], body["n_shared_experts"],
        body["routed_scaling_factor"], body["first_k_dense_replace"],
        body["moe_intermediate_size"])
    assert (cfg.rope_theta, cfg.rope_interleaved, cfg.norm_eps) == (
        body["rope_theta"], body["rope_interleave"], body["rms_norm_eps"])
    assert round(cfg.param_count * 2 / 1e9, 2) == 5.39
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "longctx-backlog.json")))
    assert traffic["kind"] == "serve-backlog"
    assert traffic["engine"] == {"b_slots": 32, "page_size": 128,
                                 "max_model_len": 8192}
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.4, "min": 2048,
        "max": 6144}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256,
        "max": 2048}
    assert traffic["parity"] == {"prompt": 3000, "decode": 16}
    assert traffic["drain_seconds"] == 60 and traffic["trace_ticks"] == 60
    # the pool a full reservation takes: 32 slots x 64 pages + the trash page
    pages = 1 + 32 * (8192 // 128)
    assert pages == 2049
    assert round(pages * 128 * 24 * 1152 / 1e9, 2) == 7.25


def _kanana_cfg():
    from deepspeed_tpu.models import get_config

    return get_config("kanana-2-30b-a3b", num_layers=24, moe_experts_held=16,
                      vocab_size=16032)


def test_mla_work_arithmetic():
    from benchmark.lib import mla_work

    cfg = _kanana_cfg()
    assert mla_work.is_latent(cfg)
    assert mla_work.latent_row_bytes(cfg) == 1152
    assert mla_work.absorbed_ops_per_row(cfg) == 69632
    assert mla_work.expert_params(cfg) == 3 * 2048 * 768
    attn = (2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
            + 2 * 2048)
    want = (24 * attn + 3 * 2048 * 6144                  # layer 0's MLP
            + 23 * (2048 * 128 + 128 + 2 * 3 * 2048 * 768)   # router, shared
            + 16032 * 2048 + 2048)                       # head, final norm
    assert mla_work.non_routed_params(cfg) == want
    # every parameter is counted once: what is left are the held experts
    # and the embedding rows a tick looks up
    assert cfg.param_count == (want + 23 * 16 * 3 * 2048 * 768
                               + 16032 * 2048)
    work = mla_work.decode_tick_work(cfg, 150_000, 290, 32, 552)
    assert work["latent_bytes"] == 150_000 * 24 * 1152
    assert work["weight_bytes"] == 2 * (want + 290 * 3 * 2048 * 768)
    assert work["flops"] == pytest.approx(
        150_000 * 24 * 69632 + 2.0 * 32 * want + 2.0 * 552 * 3 * 2048 * 768)
    a = dict(live_rows=150_000, moe_experts_touched=290,
             moe_pairs=32 * 6 * 23, moe_local_pairs=552)
    assert mla_work.tick_work(cfg, a) == work


def _latent_record():
    def decode(t0, **kw):
        return _span("serve.decode", t0, 0.028, **kw)

    spans = [
        decode(1.0, live_rows=140_000, gathered_rows=155_648, moe_rows=540,
               moe_pairs=4416, moe_local_pairs=540, moe_experts_touched=280,
               moe_experts_held=368),
        decode(2.0, live_rows=160_000, gathered_rows=172_032, moe_rows=564,
               moe_pairs=4416, moe_local_pairs=564, moe_experts_touched=300,
               moe_experts_held=368),
        # the drain: past the window's end, left out
        decode(50.0, live_rows=8, gathered_rows=8192, moe_rows=1,
               moe_pairs=138, moe_local_pairs=20, moe_experts_touched=20,
               moe_experts_held=368),
        _span("serve.prefill", 1.5, 0.2, gathered_rows=0, tokens=4000,
              bucket=4096, moe_rows=1, moe_pairs=23 * 6 * 4000,
              moe_local_pairs=69_000, moe_experts_touched=368,
              moe_experts_held=368),
    ]
    trace = {"host": [[1.0e9, 28e6, "serve.decode"],
                      [2.0e9, 28e6, "serve.decode"]],
             "modules": [[1.0e9 + 1, 26.0e6, "jit_serve_decode"],
                         [2.0e9 + 1, 28.0e6, "jit_serve_decode"]]}
    return {"spans": spans, "trace": trace, "device": {"kind": "TPU v5 lite"},
            "serve": {"t_end": 41.0, "cfg": _kanana_cfg()}}


def test_latent_readers_on_a_hand_built_record():
    from benchmark.lib import mla_work

    rec = _latent_record()
    cfg = rec["serve"]["cfg"]
    w = [mla_work.decode_tick_work(cfg, 140_000, 280, 32, 540),
         mla_work.decode_tick_work(cfg, 160_000, 300, 32, 564)]
    latent = sum(x["latent_bytes"] for x in w)
    assert _read("kv_latent_traffic_share", rec) == pytest.approx(
        100 * latent / (latent + sum(x["weight_bytes"] for x in w)))
    mean = mla_work.decode_tick_work(cfg, 150_000, 290, 32, 552)
    need = max((mean["latent_bytes"] + mean["weight_bytes"]) / 819e9,
               mean["flops"] / 197e12)
    got = _read("mla_decode_roofline", rec)
    assert got == pytest.approx(100 * need / 27.0e-3)
    assert 0 < got < 100
    # the expert layer's two readers, which this cell shares with MiMo's
    assert _read("moe_local_pair_share", rec) == pytest.approx(
        100 * (540 + 564 + 69_000) / (4416 * 2 + 552_000))
    assert _read("moe_held_touched_share", rec) == pytest.approx(
        100 * (280 + 300) / (368 * 2))
    assert _read("kv_gather_useful_share.capacity", rec) == pytest.approx(
        100 * (300_000 + 8) / (155_648 + 172_032 + 8192))


@pytest.mark.parametrize("name", ["kv_latent_traffic_share",
                                  "mla_decode_roofline"])
def test_latent_readers_read_nothing_from_a_program_without_the_leaf(name):
    """The parent's program has no ``kv_lora_rank`` and another model's
    spans are not a latent model's: each reader returns None and does not
    raise, with a trace or without."""
    from deepspeed_tpu.models import get_config

    rec = _latent_record()
    other = dict(rec, serve={"t_end": 41.0,
                             "cfg": get_config("olmoe-1b-7b", num_layers=12)})
    assert _read(name, other) is None
    bare = types.SimpleNamespace(num_layers=24, hidden_size=2048)
    assert _read(name, dict(rec, serve={"t_end": 41.0, "cfg": bare})) is None
    old = [_span("serve.decode", 1.0, 0.01, tick=1, gathered_rows=256),
           _span("serve.prefill", 1.2, 0.02, tokens=40, bucket=64)]
    assert _read(name, dict(rec, spans=old)) is None
    assert _read(name, dict(rec, trace=None, spans=old)) is None
    assert _read(name, {"trace": None}) is None
