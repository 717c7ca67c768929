"""Median host-clock time of one ``train_batch`` call, ms."""
import statistics


def read(record):
    return statistics.median(record["train"]["step_s"]) * 1e3
