"""What a decode tick of an MoE model must move over HBM (the non-expert
weights once, the matrices of the experts its live rows touch, the K/V its
active slots hold) at the chip's published bandwidth, over the decode
program's median device time, %.

``decode_roofline`` takes every weight as needed each tick, which is wrong
where a tick reads only the experts it routes to.  Experts touched per tick:
the mean ``moe_experts_touched`` attr of the window's ``serve.decode`` spans
(summed over layers by the program).  Live K/V: the mean ``live_rows`` attr
of the same spans, the rows the active slots hold.  None where the spans
carry no such attrs."""
import statistics

from benchmark.lib import flops, moe_work, trace_reduce


def read(record):
    tr = record["trace"]
    ticks = moe_work.moe_calls(record, "serve.decode")
    if tr is None or not ticks:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    if not ms:
        return None
    live = statistics.fmean(a["live_rows"] for a in ticks)
    touched = statistics.fmean(a["moe_experts_touched"] for a in ticks)
    need = moe_work.moe_decode_tick_bytes(record["serve"]["cfg"], touched,
                                          live)
    bw = flops.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (statistics.median(ms) * 1e-3)
