"""What a decode tick of a model of one-sublayer layers with experts in a
latent must move over HBM (``lib/latent_moe_work.py``: every E layer's
router, latent projections and shared expert once and the held experts its
rows reached, the M layers', the * layers' and the head's weights once,
every live slot's state of the M layers read and written, the live K/V rows
of the * layers) at the chip's published bandwidth, over the decode
program's median device time, %.  Experts, slots and rows: the means of the
``experts_touched_held``, ``state_slots`` and ``kv_live_rows`` attrs of the
window's ``serve.decode`` spans.  None where the model is another, the spans
carry no such attrs or there is no device trace."""
import statistics

from benchmark.lib import flops, latent_moe_work, trace_reduce


def read(record):
    tr = record["trace"]
    calls = latent_moe_work.decode_calls(record)
    if tr is None or not calls:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    if not ms:
        return None
    work = latent_moe_work.decode_tick_work(
        record["serve"]["cfg"],
        statistics.fmean(a["experts_touched_held"] for a in calls),
        statistics.fmean(a["state_slots"] for a in calls),
        statistics.fmean(a["kv_live_rows"] for a in calls))
    bw = flops.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (sum(work.values()) / bw) / (statistics.median(ms) * 1e-3)
