"""Least time the chip could take for the expert matmuls of the traced
decode ticks over their device time, %.  Work from ``lib/moe_work.py``:
each touched expert's matrices read once, 6·d·f operations a live row
(``moe_experts_touched`` and ``moe_live_rows`` of the first N
``serve.decode`` spans of the window, N the decode programs in the trace:
the capture starts with the window).  A lower bound on the work, so it
cannot read over 100.  At 16 slots the bound is memory."""
from benchmark.lib import flops, moe_work


def read(record):
    tr = record["trace"]
    ticks = moe_work.moe_calls(record, "serve.decode")
    if tr is None or not ticks:
        return None
    matmul_s = moe_work.expert_matmul_device_s(tr)
    _, n = moe_work.program_device_s(tr)
    if not matmul_s or not n:
        return None
    traced = ticks[:n]
    work = moe_work.expert_matmul_work(
        record["serve"]["cfg"],
        sum(a["moe_live_rows"] for a in traced),
        sum(a["moe_experts_touched"] for a in traced))
    least, _bound = flops.roofline_seconds(work["flops"], work["bytes"],
                                           record["device"]["kind"])
    return 100.0 * least / matmul_s
