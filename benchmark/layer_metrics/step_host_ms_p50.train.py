"""Median host time of a training step, ms: the ``train.batch`` span less
its ``train.step`` span, per step (traced, ``train.step`` ends in a sync on
the loss, so what is left is data collection, the blocking reads and the
bookkeeping that run while the device has nothing to do)."""
import statistics


def read(record):
    dur = {"train.batch": {}, "train.step": {}}
    for s in record.get("spans", []):
        if s.name in dur and s.attrs and "step" in s.attrs:
            dur[s.name][s.attrs["step"]] = s.dur_s
    ms = [(b - dur["train.step"][step]) * 1e3
          for step, b in dur["train.batch"].items()
          if step in dur["train.step"]]
    return statistics.median(ms) if ms else None
