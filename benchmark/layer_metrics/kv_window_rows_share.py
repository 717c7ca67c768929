"""Of the K/V head rows the decode program's two read plans cover, the share
that is the window layers', %, over the window's decode ticks:
``kv_rows_window`` / (``kv_rows_window`` + ``kv_rows_full``) of the
``serve.decode`` spans.  The window's bound as a number: a window layer that
read every live page would hold the share of the model's K/V heads that are
its own.  None where the spans carry no such attrs."""
from benchmark.lib import hybrid_work


def read(record):
    ticks = hybrid_work.calls(record, "serve.decode", "kv_rows_window")
    total = sum(a["kv_rows_window"] + a["kv_rows_full"] for a in ticks)
    if not total:
        return None
    return 100.0 * sum(a["kv_rows_window"] for a in ticks) / total
