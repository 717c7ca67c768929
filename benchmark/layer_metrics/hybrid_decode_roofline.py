"""What a decode tick of a model with window and full layers and a held
share of experts must move over HBM (``lib/hybrid_work.py``: the non-expert
weights once, the held experts its rows reach, the live K/V head rows of
both kinds) at the chip's published bandwidth, over the decode program's
median device time, %.  Experts touched and live rows: the means of the
``moe_experts_touched``, ``kv_live_rows_full`` and ``kv_live_rows_window``
attrs of the window's ``serve.decode`` spans.  None where the spans carry
no such attrs or there is no device trace."""
import statistics

from benchmark.lib import flops, hybrid_work, trace_reduce


def read(record):
    tr = record["trace"]
    ticks = [a for a in hybrid_work.calls(record, "serve.decode",
                                          "kv_live_rows_full")
             if "moe_experts_touched" in a]
    if tr is None or not ticks:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    if not ms:
        return None
    need = hybrid_work.decode_tick_bytes(
        record["serve"]["cfg"],
        statistics.fmean(a["moe_experts_touched"] for a in ticks),
        statistics.fmean(a["kv_live_rows_full"] + a["kv_live_rows_window"]
                         for a in ticks))
    bw = flops.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (statistics.median(ms) * 1e-3)
