"""Median host time of a working tick that admitted nothing, ms: the
``serve.tick`` span less the part of its ``serve.decode`` span spent
waiting for the device (the span's duration minus its ``dispatch_ms``
attr: open to launch returned).  What is left is scheduling, the four
small uploads and the launch, the slot walk, gauges.  None where the
decode spans carry no ``dispatch_ms``."""
import statistics


def read(record):
    spans = record.get("spans", [])
    decode = {s.attrs["tick"]: s for s in spans
              if s.name == "serve.decode" and s.attrs
              and "dispatch_ms" in s.attrs}
    admits = [s.t0 for s in spans if s.name == "serve.admit"]
    host_ms = []
    for t in spans:
        if t.name != "serve.tick" or not t.attrs \
                or t.attrs.get("tick") not in decode \
                or any(t.t0 <= a < t.t0 + t.dur_s for a in admits):
            continue
        d = decode[t.attrs["tick"]]
        host_ms.append((t.dur_s - d.dur_s) * 1e3 + d.attrs["dispatch_ms"])
    return statistics.median(host_ms) if host_ms else None
