"""Rows an expert sorts a decode tick, the mean over the window's ticks:
sum of the ``moe_rows`` attrs of the ``serve.decode`` spans / sum of their
``moe_experts_held`` (experts x expert layers).  The depth of a tick's
grouped products: 16 with 128 slots live over 32 experts of which a token
takes 4.  None where the spans carry no such attrs."""
from benchmark.lib import moe_work


def read(record):
    ticks = [a for a in moe_work.moe_calls(record, "serve.decode")
             if a.get("moe_experts_held")]
    if not ticks:
        return None
    return (sum(a["moe_rows"] for a in ticks)
            / sum(a["moe_experts_held"] for a in ticks))
