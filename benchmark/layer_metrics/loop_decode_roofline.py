"""The least time the chip could take for a decode tick of a looped model
over the decode program's median device time, %: the larger of (the stack's
bytes once a PASS + the head + the live rows of a cache ``passes x layers``
deep) / the published bandwidth and (two operations a parameter a token a
pass + attention's over the live rows) / the published bf16 peak
(``lib/loop_work.py``).  Rows, bytes and slots: the means over the window's
``serve.decode`` spans.  The time is the median over the traced
``jit_serve_decode`` modules themselves, one a tick: with two ticks in
flight this engine's launches fall two into one ``serve.decode`` span and
none into the next (PERF.md, PR 44), so the programs of a span are not one
tick here.  It reads the same work whatever implements the passes.  None
where the model is not looped, the spans carry no such attrs or there is no
device trace."""
import statistics

from benchmark.lib import flops, loop_work

PROGRAM = "jit_serve_decode"


def read(record):
    tr = record["trace"]
    calls = loop_work.decode_calls(record)
    if tr is None or not calls:
        return None
    ms = [m[1] * 1e-6 for m in tr["modules"] if m[2] == PROGRAM]
    if not ms:
        return None
    work = loop_work.decode_tick_work(
        record["serve"]["cfg"],
        statistics.fmean(a["kv_bytes"] for a in calls),
        statistics.fmean(a["live_rows"] for a in calls),
        statistics.fmean(a["own_slots"] for a in calls))
    least, _ = flops.roofline_seconds(
        work["flops"], work["kv_bytes"] + work["weight_bytes"],
        record["device"]["kind"])
    return 100.0 * least / (statistics.median(ms) * 1e-3)
