"""Median over finished requests of ``RequestResult.ttft_s`` (due time to
first token), ms.  The median and not the p90 that every run prints on an
earlier line: in the traced run the profiler's stop blocks the engine for a
second or two once, which the few requests behind a p90 of ~40 feel and
the median does not."""
import statistics


def read(record):
    ttft = [r.ttft_s for r in record["serve"]["results"]
            if r.finish_reason in ("length", "eos")]
    return statistics.median(ttft) * 1e3 if ttft else None
