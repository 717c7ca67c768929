"""Rows of real tokens over rows the expert matmuls computed, %, over the
window's decode and prefill calls: sum ``moe_live_rows`` / sum ``moe_rows``
of the ``serve.decode`` and ``serve.prefill`` spans.  100 when a prompt's
padding and a tick's idle slots take no row.  None where the spans carry no
such attrs."""
from benchmark.lib import moe_work


def read(record):
    calls = (moe_work.moe_calls(record, "serve.decode")
             + moe_work.moe_calls(record, "serve.prefill"))
    rows = sum(a["moe_rows"] for a in calls)
    if not rows:
        return None
    return 100.0 * sum(a["moe_live_rows"] for a in calls) / rows
