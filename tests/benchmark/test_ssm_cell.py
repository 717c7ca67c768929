"""The cell of ISSUE 40 (``falcon-h1-34b-d5.chatburst-backlog``): the plain
reference's own tests, the configuration's file against the catalog's numbers
and its own arithmetic, ``lib/ssm_work.py``'s arithmetic, the three readers
of a model with state-space layers on hand-built records (and on a program
that has no such counters), and the cell end to end at a tiny size on the
CPU (``--rehearse --trace 1``)."""
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import reference_falcon_h1 as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "falcon-h1-34b-d5.chatburst-backlog"
NEW = {"ssm_state_traffic_share", "ssm_decode_roofline",
       "ssm_prefill_roofline"}


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
                           "falcon-h1-34b-d5.json")) as f:
        return json.load(f)


def _rehearse_cfg(**over):
    from deepspeed_tpu.models import get_config

    spec = _body()["rehearse_transformer_config"]
    return get_config(spec["base"], **{**spec["overrides"], **over})


def _falcon_cfg():
    from deepspeed_tpu.models import get_config

    return get_config("falcon-h1-34b", num_layers=5)


# ------------------------------------------------------------- the reference

@pytest.fixture(scope="module")
def tiny():
    from deepspeed_tpu.models import init_params

    cfg = _rehearse_cfg(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


def test_the_references_recurrence_by_hand(tiny):
    """One head, one channel: the mixer's state after each position is the
    closed form sum_j exp(A sum_{j<k<=t} dt_k) dt_j x_j B_j, whatever
    positions it is asked to keep, and keeping changes no output."""
    cfg, params = tiny
    s = R.spec(cfg)
    lp = R._layer(params, 0)
    n = jnp.asarray(np.random.default_rng(0).standard_normal((11, 64)),
                    jnp.float32)
    out, kept = R.mixer(s, lp, n, keep=(3, 10))
    again, last = R.mixer(s, lp, n, keep=(10,))
    np.testing.assert_allclose(out, again, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(kept[1], last[0], rtol=1e-5, atol=1e-6)
    # the closed form, from the mixer's own inputs recomputed in numpy
    H, P, N, G = 4, 8, 16, 2
    p = np.asarray((n * s["ssm_in_mult"]) @ lp["ssm_in"], np.float64)
    mz, mx, mb, mc, mdt = s["ssm_mults"]
    xbc = np.concatenate([p[:, 32:64] * mx, p[:, 64:96] * mb,
                          p[:, 96:128] * mc], -1)
    ext = np.concatenate([np.zeros((3, 96)), xbc])
    w = np.asarray(lp["ssm_conv_w"], np.float64)
    conv = sum(ext[k:k + 11] * w[k] for k in range(4)) + np.asarray(
        lp["ssm_conv_b"], np.float64)
    conv = conv / (1 + np.exp(-conv))
    x = conv[:, :32].reshape(11, H, P)
    B = conv[:, 32:64].reshape(11, G, N)
    dt = np.log1p(np.exp(p[:, 128:] * mdt + np.asarray(lp["ssm_dt_bias"])))
    A = -np.exp(np.asarray(lp["ssm_A_log"], np.float64))
    h, t = 3, 3
    want = sum(np.exp(A[h] * dt[j + 1:t + 1, h].sum()) * dt[j, h]
               * np.outer(x[j, h], B[j, h // 2]) for j in range(t + 1))
    np.testing.assert_allclose(kept[0][h], want, rtol=2e-4, atol=1e-7)


MUTATIONS = {
    "norm before the gate": {"norm_after_gate": False},
    "no convolution bias": {"conv_bias": False},
    "no D skip": {"skip_d": False},
    "ssm_multipliers in another order": {
        "ssm_mults": (1.2, 0.9, 0.8, 1.1, 0.7)},
    "keys unscaled": {"key_mult": 1.0},
    "one group": {"ssm_groups": 1},
}


def test_the_system_passes_the_layer_checks_at_a_tiny_size(tiny):
    cfg, params = tiny
    checks = R.layer_checks(cfg, params, seed=5, n_prompt=21, block_tokens=32,
                            n_decode=24, page_size=16)
    assert set(checks) == {"block_padded_prompt", "state_after_prefill",
                           "state_after_decode", "logits_after_decode",
                           "other_slots_untouched"}
    for name, c in checks.items():
        assert c["rel_err"] <= min(c["tol"], 1e-5), (name, c)


@pytest.mark.parametrize("what", list(MUTATIONS))
def test_a_mutated_reference_is_told_from_the_system(tiny, what):
    """Each departure from the equations moves the logits of the reference
    away from the system's by a thousand times what rounding does."""
    from deepspeed_tpu.models import transformer as system

    cfg, params = tiny
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, 40)),
                       jnp.int32)
    got = system.forward(cfg, params, toks)[0]
    assert R.rel_err(got, R.reference_logits(cfg, params, toks[0])) < 1e-5
    if what == "one group":
        with pytest.raises((TypeError, ValueError)):
            R.reference_logits(cfg, params, toks[0], **MUTATIONS[what])
        return
    assert R.rel_err(got, R.reference_logits(
        cfg, params, toks[0], **MUTATIONS[what])) > 2e-3


def test_a_state_kept_in_bfloat16_reads_worse_than_one_in_float32(tiny):
    """What the 256-step check is for: the reference itself, its state
    rounded to bfloat16 after every position, drifts from its float32 self
    by more than float32 rounding explains."""
    cfg, params = tiny
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, (200,)),
                       jnp.int32)
    _, want = R.forward(cfg, params, toks, keep=(199,))
    _, low = R.forward(cfg, params, toks, keep=(199,),
                       state_dtype=jnp.bfloat16)
    err = max(R.rel_err(low[i][0], want[i][0]) for i in range(cfg.num_layers))
    assert 1e-3 < err < 0.1
    with pytest.raises(NotImplementedError, match="Falcon-H1 block only"):
        from deepspeed_tpu.models import get_config

        R.reference_logits(get_config("tiny"), params, toks)


# ---------------------------------------------------------- the data files

def test_the_configuration_is_the_catalogs_with_the_depth_cut(manifest):
    body = _body()
    published = {      # the catalog's ``config``, every number of it
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_hidden_layers": 72, "num_key_value_heads": 4,
        "num_logits_to_keep": 1, "projectors_bias": False,
        "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120}
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["published"] == {"num_hidden_layers": 72}
    assert body["num_hidden_layers"] == 5
    for key, value in published.items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    assert body["reference"] == "benchmark.lib.reference_falcon_h1"
    assert len(body["assumed"]) >= 8
    for said in ("float32", "norm_before_gate false", "z, x, B, C, dt",
                 "unclamped", "half-split", "log U(1, 16)", "[1e-3, 1e-1]",
                 "D = 1", "U(+-1/2)"):
        assert any(said in a for a in body["assumed"]), said
    for said in ("layers shared by 1 chip", "430.12 M", "9.65 GB",
                 "4,225,024 B", "2.03 GB", "1,537 pages", "2.01 GB",
                 "13.7 GB of 16"):
        assert said in body["deployment"], said
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "falcon-h1-34b-d5")
    assert entry["reduced"] == body["reduced"]
    assert entry["source"] == body["source"]

    from benchmark.lib import system

    cfg = system.transformer_config(body, rehearse=False)
    assert (cfg.num_layers, cfg.kv_heads, cfg.dims_per_head, cfg.rope_theta,
            cfg.norm_eps) == (5, body["num_key_value_heads"],
                              body["head_dim"], body["rope_theta"],
                              body["rms_norm_eps"])
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (
        body["mamba_n_heads"], body["mamba_d_head"], body["mamba_d_state"],
        body["mamba_n_groups"], body["mamba_d_conv"],
        body["mamba_chunk_size"])
    assert cfg.ssm_heads * cfg.ssm_head_dim == body["mamba_d_ssm"]
    assert (cfg.embed_multiplier, cfg.lm_head_multiplier,
            cfg.attn_in_multiplier, cfg.attn_out_multiplier,
            cfg.key_multiplier, cfg.ssm_in_multiplier,
            cfg.ssm_out_multiplier) == (
        body["embedding_multiplier"], body["lm_head_multiplier"],
        body["attention_in_multiplier"], body["attention_out_multiplier"],
        body["key_multiplier"], body["ssm_in_multiplier"],
        body["ssm_out_multiplier"])
    assert list(cfg.ssm_multipliers) == body["ssm_multipliers"]
    assert list(cfg.mlp_multipliers) == body["mlp_multipliers"]
    # the file's arithmetic: 9.65 GB of weights, 2.03 GB of state, 2.01 GB
    # of K/V in 1,537 pages
    assert round(cfg.param_count * 2 / 1e9, 2) == 9.65
    slots, pages = 96, 1 + 96 * (2048 // 128)
    assert pages == 1537
    assert 32 * 128 * 256 * 4 + 3 * 5120 * 2 == 4_225_024
    assert round(slots * 5 * 4_225_024 / 1e9, 2) == 2.03
    assert round(pages * 128 * 5 * 2048 / 1e9, 2) == 2.01
    # every multiplier of the rehearsal away from 1, 2 groups, chunk 8, a
    # parity prompt that is no multiple of it
    small = system.transformer_config(body, rehearse=True)
    mults = (small.embed_multiplier, small.lm_head_multiplier,
             small.attn_in_multiplier, small.attn_out_multiplier,
             small.key_multiplier, small.ssm_in_multiplier,
             small.ssm_out_multiplier, *small.ssm_multipliers,
             *small.mlp_multipliers)
    assert all(m != 1.0 for m in mults)
    assert (small.ssm_groups, small.ssm_chunk) == (2, 8)


def test_the_traffic_is_what_issue_40_names():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "chatburst-backlog.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "serve-backlog"
    assert traffic["engine"] == {"b_slots": 96, "page_size": 128,
                                 "max_model_len": 2048}
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32,
        "max": 1024}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.6, "min": 32,
        "max": 768}
    assert traffic["parity"] == {"prompt": 700, "decode": 32}
    assert traffic["parity"]["prompt"] % 128 and 512 < 700 < 1024
    assert traffic["drain_seconds"] == 60 and traffic["trace_ticks"] == 60
    assert "sized on the chip" in traffic["notes"]
    assert traffic["rehearse"]["parity"]["prompt"] % 8


def test_the_cell_reports_what_issue_40_lists(manifest):
    reported = {m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", [])}
    assert reported >= NEW | {
        "gap_fetch_share", "gap_host_share", "gap_launch_share",
        "host_busy_share", "slots_active_mean", "window_compiles.serve",
        "device_idle_share.serve", "peak_hbm_gb.serve",
        "host_bound_idle_share.serve", "prefill_pad_share", "prefill_ms_p50",
        "admit_host_ms_p50", "decode_ms_p50.capacity",
        "tick_host_ms_p50.capacity", "kv_gather_useful_share.capacity"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, source in (
            ("ssm_state_traffic_share", "paged forward", "program_counter"),
            ("ssm_decode_roofline", "paged forward", "device_trace"),
            ("ssm_prefill_roofline", "serving executor", "device_trace")):
        m = by_name[name]
        assert CELL in m["workloads"]
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == (layer, source, "serve_tokens_per_s", "%",
                                 "higher")
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "chatburst-backlog"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]


# ------------------------------------------------- ssm_work and the readers

def test_ssm_work_arithmetic():
    from benchmark.lib import ssm_work

    cfg = _falcon_cfg()
    assert ssm_work.is_ssm(cfg)
    assert ssm_work.conv_channels(cfg) == 5120
    assert ssm_work.state_bytes(cfg) == 4_225_024
    assert ssm_work.kv_row_bytes(cfg) == 2048
    attn = 2 * 5120 * 2560 + 2 * 5120 * 512
    mixer = (5120 * 9248 + 4096 * 5120 + 5120 * 5 + 96 + 4096)
    assert (attn, mixer) == (31_457_280, 68_351_072)
    assert ssm_work.layer_params(cfg) == attn + mixer + 3 * 5120 * 21504 \
        + 2 * 5120 == 430_120_032
    streamed = 5 * 430_120_032 + 5120 + 261120 * 5120
    assert ssm_work.streamed_params(cfg) == streamed
    # every parameter once: what is left is the embedding a token looks up
    assert cfg.param_count == streamed + 261120 * 5120
    assert ssm_work.attention_ops_per_row(cfg) == 10240
    assert ssm_work.recurrence_ops(cfg) == 5 * 32 * 128 * 256 + 8 * 5120
    work = ssm_work.decode_tick_work(cfg, 40_000, 96)
    # the table of ISSUE 40: 4.06 GB of state a tick of 96 slots
    assert work["state_bytes"] == 2 * 96 * 5 * 4_225_024
    assert round(work["state_bytes"] / 1e9, 2) == 4.06
    assert work["kv_bytes"] == 40_000 * 5 * 2048
    assert work["weight_bytes"] == 2 * streamed
    assert work["flops"] == pytest.approx(
        2.0 * 96 * streamed + 96 * 5 * ssm_work.recurrence_ops(cfg)
        + 40_000 * 5 * 10240)
    assert ssm_work.tick_work(cfg, dict(live_rows=40_000, state_slots=96)
                              ) == work
    pre = ssm_work.prefill_work(cfg, 300)
    assert pre["bytes"] == pytest.approx(
        2 * streamed + 5 * 4_225_024 + 2 * 300 * 5 * 2048 + 2 * 300 * 5120)
    assert pre["flops"] == pytest.approx(
        2.0 * 300 * 5 * 430_120_032 + 2.0 * 261120 * 5120
        + 300 * 5 * ssm_work.recurrence_ops(cfg)
        + 300 * 301 / 2 * 5 * 10240)


def _ssm_record():
    def decode(t0, **kw):
        return _span("serve.decode", t0, 0.03, **kw)

    row = 5 * 4_225_024
    spans = [
        decode(1.0, live_rows=38_000, gathered_rows=49_152, state_slots=96,
               state_bytes=96 * row),
        decode(2.0, live_rows=42_000, gathered_rows=53_248, state_slots=96,
               state_bytes=96 * row),
        # the drain: past the window's end, left out
        decode(50.0, live_rows=300, gathered_rows=24_576, state_slots=2,
               state_bytes=2 * row),
        _span("serve.prefill", 1.2, 0.02, gathered_rows=256, tokens=200,
              bucket=256, scan_chunks=2, scan_chunks_bucket=2, state_reset=1),
        _span("serve.prefill", 1.5, 0.04, gathered_rows=768, tokens=700,
              bucket=1024, scan_chunks=6, scan_chunks_bucket=8, state_reset=1),
        _span("serve.prefill", 2.5, 0.02, gathered_rows=128, tokens=90,
              bucket=128, scan_chunks=1, scan_chunks_bucket=1, state_reset=1),
    ]
    # the trace holds the first two prefills and both ticks
    trace = {"host": [[1.0e9, 30e6, "serve.decode"],
                      [1.2e9, 20e6, "serve.prefill"],
                      [1.5e9, 40e6, "serve.prefill"],
                      [2.0e9, 30e6, "serve.decode"]],
             "modules": [[1.0e9 + 1, 27.0e6, "jit_serve_decode"],
                         [1.2e9 + 1, 18.0e6, "jit_serve_prefill_256"],
                         [1.5e9 + 1, 38.0e6, "jit_serve_prefill_1024"],
                         [2.0e9 + 1, 29.0e6, "jit_serve_decode"]]}
    return {"spans": spans, "trace": trace, "device": {"kind": "TPU v5 lite"},
            "serve": {"t_end": 41.0, "cfg": _falcon_cfg()}}


def test_ssm_readers_on_a_hand_built_record():
    from benchmark.lib import ssm_work

    rec = _ssm_record()
    cfg = rec["serve"]["cfg"]
    w = [ssm_work.decode_tick_work(cfg, 38_000, 96),
         ssm_work.decode_tick_work(cfg, 42_000, 96)]
    state = sum(x["state_bytes"] for x in w)
    share = _read("ssm_state_traffic_share", rec)
    assert share == pytest.approx(100 * state / (state + sum(
        x["kv_bytes"] + x["weight_bytes"] for x in w)))
    assert 30 < share < 45
    mean = ssm_work.decode_tick_work(cfg, 40_000, 96)
    need = max((mean["state_bytes"] + mean["kv_bytes"]
                + mean["weight_bytes"]) / 819e9, mean["flops"] / 197e12)
    got = _read("ssm_decode_roofline", rec)
    assert got == pytest.approx(100 * need / 28.0e-3)
    assert 0 < got < 100
    least = 0.0
    for tokens in (200, 700):       # the two prefills the trace holds
        p = ssm_work.prefill_work(cfg, tokens)
        least += max(p["bytes"] / 819e9, p["flops"] / 197e12)
    got = _read("ssm_prefill_roofline", rec)
    assert got == pytest.approx(100 * least / 56.0e-3)
    assert 0 < got < 100
    assert 0 < _read("kv_gather_useful_share.capacity", rec) < 100


@pytest.mark.parametrize("name", sorted(NEW))
def test_ssm_readers_read_nothing_from_a_program_without_the_state(name):
    """The parent's program has no ``ssm_heads`` and another model's spans
    are not this model's: each reader returns None and does not raise, with
    a trace or without."""
    from deepspeed_tpu.models import get_config

    rec = _ssm_record()
    other = dict(rec, serve={"t_end": 41.0,
                             "cfg": get_config("olmoe-1b-7b", num_layers=12)})
    assert _read(name, other) is None
    bare = types.SimpleNamespace(num_layers=5, hidden_size=5120)
    assert _read(name, dict(rec, serve={"t_end": 41.0, "cfg": bare})) is None
    old = [_span("serve.decode", 1.0, 0.01, tick=1, live_rows=9,
                 gathered_rows=256),
           _span("serve.prefill", 1.2, 0.02, tokens=40, bucket=64)]
    assert _read(name, dict(rec, spans=old)) is None
    assert _read(name, dict(rec, trace=None, spans=old)) is None
    assert _read(name, {"trace": None}) is None


def test_rehearse_the_cell(capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 40),
                             "--seconds", "1.5", "--trace", "1", "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"ssm_state_traffic_share", "kv_gather_useful_share.capacity",
            "slots_active_mean", "window_compiles.serve",
            "prefill_pad_share", "tick_host_ms_p50.capacity",
            "admit_host_ms_p50", "host_busy_share",
            "cache_misses"} <= set(res["metric_names"])
    # no device metric from a CPU run
    assert not {n for n in res["metric_names"] if "roofline" in n}
    checks = _last(out, "checks")
    assert checks["logits_match_reference"]
    assert checks["layers_match_reference"] and checks["pages_balanced"]
    note = [json.loads(ln[5:]) for ln in out.splitlines()
            if ln.startswith("note ") and "lookahead_launched_dropped" in ln]
    launched, dropped = note[-1]["lookahead_launched_dropped"]
    assert launched > 0 and dropped == 0
