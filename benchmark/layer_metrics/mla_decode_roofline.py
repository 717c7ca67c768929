"""The least time the chip could take for a decode tick of a
latent-attention model over the decode program's median device time, %:
the larger of (weights read + live latent rows) / the published bandwidth
and (the absorbed products over the live rows + two operations a parameter
a token passes through) / the published bf16 peak (``lib/mla_work.py``; the
absorbed read sits at the chip's ridge, so either may bound it).  Rows,
experts touched and pairs: the means over the window's ``serve.decode``
spans.  None where the model has no latent cache, the spans carry no such
attrs or there is no device trace."""
import statistics

from benchmark.lib import flops, mla_work, trace_reduce


def read(record):
    tr = record["trace"]
    calls = mla_work.decode_calls(record)
    if tr is None or not calls:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    if not ms:
        return None
    cfg = record["serve"]["cfg"]
    mean = {k: statistics.fmean(a[k] for a in calls)
            for k in ("live_rows", "moe_experts_touched", "moe_pairs",
                      "moe_local_pairs")}
    work = mla_work.tick_work(cfg, mean)
    least, _ = flops.roofline_seconds(
        work["flops"], work["latent_bytes"] + work["weight_bytes"],
        record["device"]["kind"])
    return 100.0 * least / (statistics.median(ms) * 1e-3)
