"""The least time the chip could take for the traced prefills of a model
with state-space layers, each at its REAL tokens (the scan, the head over
one position and causal attention included; ``lib/ssm_work.py``), over the
device time of their programs, %.  The trace holds the window's first
prefills: the k-th ``serve.prefill`` span of the trace is the k-th the
program recorded, and the sums run over those both have.  A bucket's padding
and a scan that runs every chunk of the bucket read as a lower share.  None
where the model has no state-space layers, the spans carry no such attrs or
there is no device trace."""
from benchmark.lib import flops, ssm_work, trace_reduce


def read(record):
    tr = record["trace"]
    calls = ssm_work.prefill_calls(record)
    if tr is None or not calls:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.prefill")
    n = min(len(ms), len(calls))
    if not n:
        return None
    cfg, kind = record["serve"]["cfg"], record["device"]["kind"]
    least = 0.0
    for a in calls[:n]:
        work = ssm_work.prefill_work(cfg, a["tokens"])
        least += flops.roofline_seconds(work["flops"], work["bytes"], kind)[0]
    return 100.0 * least / (sum(ms[:n]) * 1e-3)
