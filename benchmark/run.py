#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks every name up in ``BENCHMARK.json`` and the files beside this one, and
branches on none: a cell is a configuration file plus a traffic file, a
traffic kind is a module under ``traffic_kinds/``, a per-layer metric is a
reader under ``layer_metrics/``.  See ``benchmark/README.md``.

Prints progress and notes on earlier lines; the last line of stdout is the
result object and nothing more.  ``--rehearse`` (with ``JAX_PLATFORMS=cpu``)
runs the same code at the tiny size each data file carries under
``rehearse`` and prints counts only, never a device metric.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

T_PROCESS_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Context:
    """What a traffic kind is handed: the cell's data, the arguments, and
    the two calls that bracket the measured window."""

    def __init__(self, args, cell, config, traffic, meter):
        self.args, self.cell = args, cell
        self.config, self.traffic = config, traffic
        self.meter = meter
        self.trace_dir = None

    def note(self, **kv):
        """Detail for the earlier lines of the output; never judged."""
        print("note", json.dumps(kv, default=str), flush=True)

    def start_window(self, trace_units: int):
        """Set-up ends here.  With ``--trace 1`` the host tracer goes on
        and the device profiler captures the first ``trace_units`` steps or
        working ticks (the program's own ``device_trace_unit`` countdown)."""
        self.setup_cache_misses = self.meter.cache_misses()
        if self.args.trace:
            from deepspeed_tpu.observability import (capture_device_trace,
                                                     configure_tracer)

            configure_tracer(enabled=True, capacity=1 << 17)
            self.trace_dir = os.path.join(ROOT, ".bench_trace",
                                          self.cell["name"])
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            capture_device_trace(self.trace_dir, n_units=trace_units)
        self._c0 = self.meter.compiles()
        self.t_window = time.monotonic()
        self.setup_s = self.t_window - T_PROCESS_START

    def end_window(self):
        self.window_s = time.monotonic() - self.t_window
        self.window_compiles = self.meter.compiles() - self._c0
        if self.args.trace:
            from deepspeed_tpu.observability import stop_device_trace

            stop_device_trace()     # no-op when the countdown already did


def reader_path(name: str) -> str:
    """``layer_metrics/<name>.py``; a metric split by the end-to-end metric
    it moves (``peak_hbm_gb.train``, ``peak_hbm_gb.serve``) shares the one
    reader named before its last dot (``peak_hbm_gb.py``)."""
    here = os.path.join(ROOT, "benchmark", "layer_metrics")
    path = os.path.join(here, name + ".py")
    if not os.path.isfile(path) and "." in name:
        path = os.path.join(here, name.rsplit(".", 1)[0] + ".py")
    return path


def _load_reader(name: str):
    path = reader_path(name)
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reported(metric, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; counts only, no device metric")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        traffic = {**traffic, **traffic.get("rehearse", {})}
        # before jax is imported; all three asked for by name
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DS_TPU_PALLAS_INTERPRET"] = "1"
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell['chips']}"
            ).strip()

    from benchmark.lib import system

    device = system.device_record(cell["chips"], args.rehearse)
    if not args.rehearse:
        # the program's own placement: JAX_COMPILATION_CACHE_DIR if set,
        # else the fixed .jax_compile_cache/ inside this checkout
        from deepspeed_tpu.utils.compile_cache import place_compile_cache

        print("compile cache:", place_compile_cache(), flush=True)
    meter = system.Meter()
    ctx = Context(args, cell, config, traffic, meter)
    kind = importlib.import_module(
        "benchmark.traffic_kinds." + traffic["kind"].replace("-", "_"))
    record = kind.run(ctx)
    record.update(cell=cell, device=device, trace=None,
                  setup_s=ctx.setup_s, window_s=ctx.window_s,
                  window_compiles=ctx.window_compiles,
                  setup_cache_misses=ctx.setup_cache_misses,
                  rehearse=args.rehearse)
    if args.trace:
        from deepspeed_tpu.observability import Span, get_tracer

        record["spans"] = [s for s in get_tracer().recorder.snapshot()
                           if isinstance(s, Span) and s.t0 >= ctx.t_window]
    record["checks"]["no_compile_in_window"] = ctx.window_compiles == 0
    record["end_to_end"]["setup_s"] = ctx.setup_s

    out = {"correct": all(record["checks"].values()),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"])}
    print("checks", json.dumps(record["checks"]), flush=True)
    dev_out = dict(device)
    metrics = {}
    if args.trace:
        from benchmark.lib import trace_reduce

        # None where the trace holds no TPU plane (the CPU rehearsal)
        record["trace"] = trace_reduce.reduce_dir(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        for m in manifest["per_layer"]:
            if not _reported(m, cell["name"]):
                continue
            value = _load_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if record["trace"] is not None:
            dev_out["busy_s"] = record["trace"]["busy_s"]
            dev_out["window_s"] = record["trace"]["window_s"]
            out["breakdown"] = trace_reduce.breakdown(record["trace"])
    else:
        for m in manifest["end_to_end"]:
            if _reported(m, cell["name"]):
                metrics[m["name"]] = {
                    "value": float(record["end_to_end"][m["name"]]),
                    "unit": m["unit"]}
    if args.rehearse:
        # a CPU run yields counts, never a device metric
        print("rehearsal", json.dumps(
            {**out, "metric_names": sorted(metrics), "device": device}))
        return 0
    dev_out["memory_peak_bytes"] = system.memory_peak_bytes(cell["chips"])
    out["metrics"] = metrics
    out["device"] = dev_out
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
