"""Held experts with at least one row over the experts held, a layer-tick,
%, over the window's decode ticks: ``moe_experts_touched`` /
``moe_experts_held`` of the ``serve.decode`` spans.  What share of the held
experts' weights a tick has to stream.  None where the spans carry no such
attrs."""
from benchmark.lib import hybrid_work


def read(record):
    ticks = hybrid_work.calls(record, "serve.decode", "moe_experts_held")
    held = sum(a["moe_experts_held"] for a in ticks)
    if not held:
        return None
    return 100.0 * sum(a["moe_experts_touched"] for a in ticks) / held
