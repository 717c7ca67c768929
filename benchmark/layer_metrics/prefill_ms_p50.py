"""Median device time of the prefill programs per invocation, ms: the
modules launched inside each ``serve.prefill`` span of the trace."""
import statistics

from benchmark.lib import trace_reduce


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.prefill")
    return statistics.median(ms) if ms else None
