"""Experts with at least one live row over all the experts there are, %,
over the window's decode ticks: sum of the ``moe_experts_touched`` attrs of
the ``serve.decode`` spans / (layers x experts x ticks).  What share of the
expert weights a tick has to stream.  None where the spans carry no such
attr."""
from benchmark.lib import moe_work


def read(record):
    ticks = moe_work.moe_calls(record, "serve.decode")
    if not ticks:
        return None
    cfg = record["serve"]["cfg"]
    return (100.0 * sum(a["moe_experts_touched"] for a in ticks)
            / (cfg.num_layers * cfg.num_experts * len(ticks)))
