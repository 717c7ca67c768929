"""Median device time of the decode program per invocation, ms: the
modules launched inside each ``serve.decode`` span of the trace."""
import statistics

from benchmark.lib import trace_reduce


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    return statistics.median(ms) if ms else None
