"""The least time the chip could take for a decode tick of a model of
gated-delta-rule layers and attention layers over the decode program's
median device time, %: the cell's share of the whole step.  The larger of
(the streamed weights once + every live slot's matrix states and tails of the
delta layers read and written + the live K/V rows of the attention layers) /
the published bandwidth and (two operations a parameter a token + the
recurrence's + attention's over the live rows) / the published bf16 peak
(``lib/delta_work.py``).  Slots and rows: the means of the ``state_slots``
and ``kv_live_rows`` attrs of the window's ``serve.decode`` spans.  None
where the model is another, the spans carry no such attrs or there is no
device trace."""
import statistics

from benchmark.lib import delta_work, flops, trace_reduce


def read(record):
    tr = record["trace"]
    calls = delta_work.decode_calls(record)
    if tr is None or not calls:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    if not ms:
        return None
    work = delta_work.decode_tick_work(
        record["serve"]["cfg"],
        statistics.fmean(a["state_slots"] for a in calls),
        statistics.fmean(a["kv_live_rows"] for a in calls))
    least, _ = flops.roofline_seconds(
        work["flops"],
        work["weight_bytes"] + work["state_bytes"] + work["kv_bytes"],
        record["device"]["kind"])
    return 100.0 * least / (statistics.median(ms) * 1e-3)
