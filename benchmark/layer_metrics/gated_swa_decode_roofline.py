"""What a decode tick of a model of gated window and full attention layers
with four norms, leading dense MLPs and a held share of routed experts
beside a shared one must move over HBM (``lib/gated_swa_work.py``: every
layer's attention with its gate, the norms, dense MLPs, routers, shared
expert and head once, the held experts its rows reached, the live K/V head
rows of the full layers and, under the window's bound, of the window
layers) at the chip's published bandwidth, over the decode program's median
device time, %: the whole tick's share of its roofline, memory-bound.
Experts and rows: the means of the ``moe_experts_touched``,
``kv_live_rows_full`` and ``kv_live_rows_window`` attrs of the window's
``serve.decode`` spans.  None where the model is another, the spans carry no
such attrs or there is no device trace."""
import statistics

from benchmark.lib import flops, gated_swa_work, trace_reduce


def read(record):
    tr = record["trace"]
    calls = gated_swa_work.decode_calls(record)
    if tr is None or not calls:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    if not ms:
        return None
    work = gated_swa_work.decode_tick_work(
        record["serve"]["cfg"],
        statistics.fmean(a["moe_experts_touched"] for a in calls),
        statistics.fmean(a["kv_live_rows_full"] for a in calls),
        statistics.fmean(a["kv_live_rows_window"] for a in calls))
    bw = flops.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (sum(work.values()) / bw) / (statistics.median(ms) * 1e-3)
