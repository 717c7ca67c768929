"""The yardstick's arithmetic: rates, percentiles, arrivals, operations and
bytes, against hand-worked values."""
import json
import os

import numpy as np
import pytest

from benchmark.lib import arrivals, flops, stats, system

ROOT = system.ROOT


def _cfg(name):
    return system.transformer_config(
        system.load_json("configs", name + ".json"), rehearse=False)


@pytest.mark.parametrize("n_steps", [40, 41, 97])
def test_whole_step_rate_ignores_the_step_count(n_steps):
    """40 and 41 steps of equal length give the same rate — the repair of
    PR 22's one-step-at-the-window-edge spread: the time is that of the
    steps counted, never a fixed window."""
    rate = stats.whole_step_rate(n_steps, 8192, 0.3668 * n_steps, 1)
    assert rate == pytest.approx(8192 / 0.3668)
    assert stats.median_step_rate([0.3668] * n_steps, 8192, 1) == \
        pytest.approx(rate)


def test_whole_step_rate_shows_a_stall_and_the_median_hides_it():
    """The judged rate is all the work over all the time: a one-second stall
    inside a step, or between two steps, lowers it; the median step does
    not move."""
    steps = [0.25] * 40 + [1.25]
    assert stats.median_step_rate(steps, 8192, 4) == pytest.approx(8192.0)
    assert stats.whole_step_rate(41, 8192, sum(steps), 4) == pytest.approx(
        2048 * 41 / 11.25)
    between = sum([0.25] * 41) + 1.0
    assert stats.whole_step_rate(41, 8192, between, 4) == pytest.approx(
        2048 * 41 / 11.25)


@pytest.mark.parametrize("q,want", [(0.5, 2.5), (0.9, 3.7), (0.0, 1.0),
                                    (1.0, 4.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
    assert stats.percentile([], q) is None


def test_spread_is_the_contract_s():
    import statistics

    xs = [10.0, 10.1, 9.9, 10.05, 9.95, 10.2]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


@pytest.mark.parametrize("mix", ["chat-openloop", "longprompt-openloop"])
def test_arrivals_fixed_work_any_seed(mix):
    """The schedule is the traffic file's: the same for every call, a prefix
    of a longer window's, inside the window; the rate is the file's number."""
    t = system.load_json("traffic", mix + ".json")
    a = arrivals.open_loop_schedule(t, 40.0)
    assert a == arrivals.open_loop_schedule(t, 40.0)
    assert [x[0] for x in a] == [
        x[0] for x in arrivals.open_loop_schedule(t, 51.0)][:len(a)]
    assert all(0 <= x[0] < 40.0 for x in a) and len(a) >= 30
    other = dict(t, arrival_seed=t["arrival_seed"] + 1)
    assert [x[0] for x in arrivals.open_loop_schedule(other, 40.0)] != \
        [x[0] for x in a]
    long = arrivals.poisson_arrivals(t["rate_per_s"], 4000.0, 1)
    assert len(long) / 4000.0 == pytest.approx(t["rate_per_s"], rel=0.05)
    for _, p, o in a:
        assert t["prompt_tokens"]["min"] <= p <= t["prompt_tokens"]["max"]
        assert t["output_tokens"]["min"] <= o <= t["output_tokens"]["max"]
        assert p + o <= t["engine"]["max_model_len"]


def test_lognormal_median():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 1,
            "max": 10 ** 6}
    x = arrivals.draw_lengths(spec, 20000, np.random.default_rng(0))
    assert np.median(x) == pytest.approx(256, rel=0.05)


# hand-worked: per layer 4 d^2 + 2 d f = 4*2048^2 + 2*2048*8192 = 50,331,648
@pytest.mark.parametrize("name,layers,vocab,seq,per_token", [
    # 24*50331648 + 2048*50272 = 1,310,916,608; 6N + 6*24*2048*2048
    ("opt-1.3b", 24, 50272, 2048, 6 * 1310916608 + 6 * 24 * 2048 * 2048),
    # 10*50331648 + 2048*50304 = 606,339,072; 6N + 6*10*2048*2048
    ("pythia-1.4b-d10", 10, 50304, 2048, 6 * 606339072 + 6 * 10 * 2048 * 2048),
])
def test_train_flops(name, layers, vocab, seq, per_token):
    cfg = _cfg(name)
    assert flops.matmul_params(cfg) == layers * 50331648 + 2048 * vocab
    assert flops.train_flops_per_token(cfg, seq) == per_token
    # 22,370 tokens/s on the 197 TFLOP/s chip
    assert flops.mfu(22370.0, cfg, seq, "TPU v5 lite") == pytest.approx(
        22370.0 * per_token / 197e12)


def test_pythia_mfu_value():
    assert 100 * flops.mfu(22370.0, _cfg("pythia-1.4b-d10"), 2048,
                           "TPU v5 lite") == pytest.approx(44.17, abs=0.01)


def test_flash_work_and_roofline():
    # one causal product: 2 * 4*16 * 2048^2 * 128 / 2 = 34,359,738,368
    w = flops.flash_step_work(4, 16, 2048, 128, layers=10, remat=True)
    prod = 34359738368.0
    assert w["flops"] == 20 * 2 * prod + 10 * 5 * prod
    tensor = 4 * 2048 * 16 * 128 * 2
    assert w["bytes"] == 20 * 4 * tensor + 10 * 8 * tensor
    assert (w["forward_invocations"], w["backward_invocations"]) == (20, 10)
    least, bound = flops.roofline_seconds(w["flops"], w["bytes"], "TPU v5 lite")
    assert bound == "compute"
    assert least == pytest.approx(90 * prod / 197e12)
    no_remat = flops.flash_step_work(4, 16, 2048, 128, layers=10, remat=False)
    assert no_remat["flops"] == 10 * 7 * prod
    assert flops.roofline_seconds(1e9, 819e9, "TPU v5 lite") == (1.0, "memory")


def test_decode_bytes():
    cfg = _cfg("opt-1.3b")
    # every parameter but the 2048 learned position rows, in bf16
    assert flops.weight_bytes(cfg) == 2 * (cfg.param_count - 2048 * 2048)
    assert flops.kv_bytes_per_token(cfg) == 2 * 24 * 32 * 64 * 2
    assert flops.decode_tick_bytes(cfg, 1000.0) == \
        flops.weight_bytes(cfg) + 1000 * 196608
    untied = _cfg("pythia-1.4b-d10")
    assert flops.weight_bytes(untied) == 2 * (untied.param_count
                                              - 50304 * 2048)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    with open(os.path.join(ROOT, "benchmark", "lib", "peaks.json")) as f:
        table = json.load(f)
    assert table["source"] and table["devices"]["TPU v5 lite"][
        "bf16_flops_per_s"] == 197e12
