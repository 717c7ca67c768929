"""The least time the chip could take for a decode tick of a model with
state-space layers over the decode program's median device time, %: the
larger of (every live slot's state read and written + live K/V rows +
weights) / the published bandwidth and (two operations a parameter a token
+ the recurrence's + attention's over the live rows) / the published bf16
peak (``lib/ssm_work.py``).  Rows and slots: the means over the window's
``serve.decode`` spans.  None where the model has no state-space layers, the
spans carry no such attrs or there is no device trace."""
import statistics

from benchmark.lib import flops, ssm_work, trace_reduce


def read(record):
    tr = record["trace"]
    calls = ssm_work.decode_calls(record)
    if tr is None or not calls:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    if not ms:
        return None
    work = ssm_work.decode_tick_work(
        record["serve"]["cfg"],
        statistics.fmean(a["live_rows"] for a in calls),
        statistics.fmean(a["state_slots"] for a in calls))
    least, _ = flops.roofline_seconds(
        work["flops"],
        work["state_bytes"] + work["kv_bytes"] + work["weight_bytes"],
        record["device"]["kind"])
    return 100.0 * least / (statistics.median(ms) * 1e-3)
