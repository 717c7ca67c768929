"""Working ticks whose admission left the head of the queue waiting for
pages with a slot free over the window's working ticks, %: the ``page_wait``
attr of the ``serve.tick`` spans.  Near 100: the pool, not the slots, bounds
the batch (``num_pages`` under the full reservation); 0 in any cell whose
pool is the full reservation.  None where the spans carry no such attr."""
from benchmark.lib import hybrid_work


def read(record):
    ticks = hybrid_work.calls(record, "serve.tick", "page_wait")
    if not ticks:
        return None
    return 100.0 * sum(a["page_wait"] for a in ticks) / len(ticks)
