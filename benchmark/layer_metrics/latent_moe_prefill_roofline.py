"""The operations of the traced prefills of a model of one-sublayer layers
with experts in a latent, each at its REAL tokens and the (token, expert)
pairs held here (``lib/latent_moe_work.py``), at the chip's published bf16
peak, over the device time of their programs, %.  The trace holds the
window's first prefills: the k-th ``serve.prefill`` span of the trace is the
k-th the program recorded, and the sums run over those both have.  A
bucket's padding reads as a lower share.  None where the model is another,
the spans carry no such attrs or there is no device trace."""
from benchmark.lib import flops, latent_moe_work, trace_reduce


def read(record):
    tr = record["trace"]
    calls = latent_moe_work.prefill_calls(record)
    if tr is None or not calls:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.prefill")
    n = min(len(ms), len(calls))
    if not n:
        return None
    cfg = record["serve"]["cfg"]
    ops = sum(latent_moe_work.prefill_flops(cfg, a["tokens"], a["pairs_held"])
              for a in calls[:n])
    peak = flops.peaks(record["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (ops / peak) / (sum(ms[:n]) * 1e-3)
