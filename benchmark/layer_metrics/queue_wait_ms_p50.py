"""Median ``RequestResult.queued_s`` (due time to slot admission), ms."""
import statistics


def read(record):
    waits = [r.queued_s for r in record["serve"]["results"]
             if r.finish_reason in ("length", "eos")]
    return statistics.median(waits) * 1e3 if waits else None
