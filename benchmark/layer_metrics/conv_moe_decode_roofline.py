"""What a decode tick of a model of gated-convolution layers and attention
layers with dense MLPs and expert layers behind them must move over HBM
(``lib/conv_moe_work.py``: the operators, dense MLPs, routers, norms and
head once, the experts its rows reached, every live slot's tail of the conv
layers read and written, the live K/V rows of the attention layers) at the
chip's published bandwidth, over the decode program's median device time, %:
the whole tick's share of its roofline.  Experts, slots and rows: the means
of the ``moe_experts_touched``, ``state_slots`` and ``kv_live_rows`` attrs of
the window's ``serve.decode`` spans.  None where the model is another, the
spans carry no such attrs or there is no device trace."""
import statistics

from benchmark.lib import conv_moe_work, flops, trace_reduce


def read(record):
    tr = record["trace"]
    calls = conv_moe_work.decode_calls(record)
    if tr is None or not calls:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    if not ms:
        return None
    work = conv_moe_work.decode_tick_work(
        record["serve"]["cfg"],
        statistics.fmean(a["moe_experts_touched"] for a in calls),
        statistics.fmean(a["state_slots"] for a in calls),
        statistics.fmean(a["kv_live_rows"] for a in calls))
    bw = flops.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (sum(work.values()) / bw) / (statistics.median(ms) * 1e-3)
