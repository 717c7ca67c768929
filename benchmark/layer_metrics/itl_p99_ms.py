"""99th percentile of the gaps between one request's consecutive output
tokens, ms: every ``diff(RequestResult.token_s)`` of the finished requests
(a streaming client's inter-token latency; a decode tick stalled behind
another request's prefill shows here and is averaged away in
``tpot_p50_ms``).  The count and the median go to a ``note`` line.  None
where results carry no per-token stamps."""
import json

from benchmark.lib import stats


def read(record):
    gaps = []
    for r in record["serve"]["results"]:
        stamps = getattr(r, "token_s", None)
        if stamps is not None and r.finish_reason in ("length", "eos"):
            gaps += [b - a for a, b in zip(stamps, stamps[1:])
                     if b - a == b - a]     # a journal-resumed token: NaN
    if not gaps:
        return None
    print("note", json.dumps({
        "itl_gaps": len(gaps),
        "itl_p50_ms": stats.median(gaps) * 1e3,
        "itl_max_ms": max(gaps) * 1e3}), flush=True)
    return stats.percentile(gaps, 0.99) * 1e3
