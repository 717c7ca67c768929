"""Least time the chip could take for the flash kernels' work over their
device time, %.  Work per step from ``lib/flops.py`` (forward 2 products,
backward 5, causal half; each invocation counted, a rematerialised forward
too), times the steps in the trace.  At S=2048, head_dim 128 the bound is
compute."""
from benchmark.lib import flops


def read(record):
    tr = record["trace"]
    if tr is None or not tr["mosaic_invocations"]:
        return None
    t = record["train"]
    cfg = t["cfg"]
    work = flops.flash_step_work(t["micro_batch"], cfg.num_heads, t["seq_len"],
                                 cfg.dims_per_head, cfg.num_layers, t["remat"])
    per_step = work["forward_invocations"] + 2 * work["backward_invocations"]
    steps = tr["mosaic_invocations"] / per_step
    least, _bound = flops.roofline_seconds(work["flops"] * steps,
                                           work["bytes"] * steps,
                                           record["device"]["kind"])
    return 100.0 * least / tr["mosaic_s"]
