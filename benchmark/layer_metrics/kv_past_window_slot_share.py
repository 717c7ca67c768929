"""Live slots that hold more rows than the window over the live slots, %,
over the window's decode ticks: ``kv_slots_past_window`` /
``kv_slots_live`` of the ``serve.decode`` spans.  The mix of the queue as a
tick sees it: a slot under the window is read alike by both kinds of layer,
one past it has wrapped its rings, and a window layer reads ``window_size``
rows of it where a full layer reads them all.  None where the spans carry
no such attrs."""
from benchmark.lib import hybrid_work


def read(record):
    ticks = hybrid_work.calls(record, "serve.decode", "kv_slots_past_window")
    live = sum(a.get("kv_slots_live", 0) for a in ticks)
    if not live:
        return None
    return 100.0 * sum(a["kv_slots_past_window"] for a in ticks) / live
