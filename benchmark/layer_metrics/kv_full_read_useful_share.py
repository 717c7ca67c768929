"""K/V head rows of the full layers that pass the mask over those their
read plan covers, %, over the window's decode ticks: ``kv_live_rows_full``
/ ``kv_rows_full`` of the ``serve.decode`` spans (the rest is each slot's
last page read whole and the list rounded up to whole steps).  None where
the spans carry no such attrs."""
from benchmark.lib import hybrid_work


def read(record):
    ticks = hybrid_work.calls(record, "serve.decode", "kv_rows_full")
    covered = sum(a["kv_rows_full"] for a in ticks)
    if not covered:
        return None
    return 100.0 * sum(a["kv_live_rows_full"] for a in ticks) / covered
