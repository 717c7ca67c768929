"""The harness end to end at a tiny size on the CPU (``--rehearse``), and a
cell, a configuration, a traffic mix, a traffic kind and a per-layer metric
added as files and entries only."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from benchmark import run as bench_run
from benchmark.lib import system

ROOT = system.ROOT


def _last(out: str, prefix: str):
    lines = [ln for ln in out.splitlines() if ln.startswith(prefix + " ")]
    return json.loads(lines[-1][len(prefix) + 1:])


@pytest.mark.parametrize("cell,trace,expect", [
    ("pythia-1.4b-d10.zero1", 0, {"train_tokens_per_s_chip", "setup_s"}),
    ("opt-1.3b.longprompt", 1, {"queue_wait_ms_p50", "slots_active_mean",
                                "ttft_p50_ms", "window_compiles.serve",
                                "cache_misses"}),
])
def test_rehearse(cell, trace, expect, capsys):
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    try:
        rc = bench_run.main(["--workload", cell, "--seed", str(2 ** 31 + 7),
                             "--seconds", "1.5", "--trace", str(trace),
                             "--rehearse"])
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    out = capsys.readouterr().out
    assert rc == 0
    res = _last(out, "rehearsal")
    assert res["correct"] is True, _last(out, "checks")
    assert res["attempted"] > 0 and res["failed"] == 0
    assert expect <= set(res["metric_names"])
    assert res["device"]["platform"] == "cpu"
    # a rehearsal never prints the contract's result object
    assert '"metrics"' not in out.splitlines()[-1]


def test_unknown_workload_is_refused(capsys):
    assert bench_run.main(["--workload", "no-such.cell"]) == 2


def test_no_tpu_no_result():
    """Without --rehearse on a host with no TPU: non-zero, no result line."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "opt-1.3b.chat", "--seconds", "1"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout


def test_extend_by_files_only(tmp_path):
    """A throwaway configuration, traffic mix, traffic kind, per-layer metric
    and cell, added beside a copy of the benchmark without editing any file
    that is there; the copied run.py finds them all by name."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    b = tmp_path / "benchmark"
    (b / "configs" / "toy.json").write_text(json.dumps({
        "source": "none", "reduced": [], "answer": 42}))
    (b / "traffic" / "toy-mix.json").write_text(json.dumps({
        "kind": "toy-kind", "units": 3, "rehearse": {"units": 2}}))
    (b / "traffic_kinds" / "toy_kind.py").write_text(textwrap.dedent('''
        def run(ctx):
            ctx.start_window(trace_units=1)
            n = ctx.traffic["units"] * ctx.config["answer"]
            ctx.end_window()
            return {"checks": {"ran": True}, "attempted": n, "failed": 0,
                    "end_to_end": {"toy_rate": float(n)}, "toy": n}
    '''))
    (b / "layer_metrics" / "toy_units.py").write_text(
        "def read(record):\n    return record['toy'] / 2\n")
    manifest["configs"].append({"name": "toy", "source": "none",
                                "file": "benchmark/configs/toy.json",
                                "reduced": [], "why": "throwaway"})
    manifest["workloads"].append({"name": "toy.cell", "config": "toy",
                                  "traffic": "toy-mix", "chips": 1,
                                  "why": "throwaway"})
    manifest["end_to_end"].append({"name": "toy_rate", "unit": "count",
                                   "better": "higher", "bound": 0.01,
                                   "source": "host_clock",
                                   "workloads": ["toy.cell"]})
    manifest["per_layer"].append({"name": "toy_units", "unit": "count",
                                  "better": "higher",
                                  "source": "program_counter", "layer": "toy",
                                  "moves": "toy_rate",
                                  "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    p = subprocess.run(
        [sys.executable, str(b / "run.py"), "--workload", "toy.cell",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path)
    assert p.returncode == 0, p.stderr[-2000:]
    res = _last(p.stdout, "rehearsal")
    assert res["correct"] and res["attempted"] == 84
    assert {"toy_units", "cache_misses"} <= set(res["metric_names"])
