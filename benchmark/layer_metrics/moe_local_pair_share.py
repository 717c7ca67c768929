"""(Token, expert) pairs whose expert is held here over all the pairs the
routers chose, %, over the window's decode ticks and prefills:
``moe_local_pairs`` / ``moe_pairs`` of the ``serve.decode`` and
``serve.prefill`` spans.  held / experts if routing is even (6.25% for 16 of
256); it moves only if routing or the share is wrong.  None where the spans
carry no such attrs."""
from benchmark.lib import hybrid_work


def read(record):
    calls = (hybrid_work.calls(record, "serve.decode", "moe_pairs")
             + hybrid_work.calls(record, "serve.prefill", "moe_pairs"))
    pairs = sum(a["moe_pairs"] for a in calls)
    if not pairs:
        return None
    return 100.0 * sum(a["moe_local_pairs"] for a in calls) / pairs
