"""Median host time of one admission, ms, per request: its
``serve.prefix_match`` and ``serve.admit`` spans less the ``serve.prefill``
span inside the latter.  The prefix index's lookup and hashing, page
allocation, and (after the first token's stamp) the index's publish."""
import statistics


def read(record):
    host = {}
    admitted = set()
    for s in record.get("spans", []):
        sign = {"serve.prefix_match": 1, "serve.admit": 1,
                "serve.prefill": -1}.get(s.name)
        if sign is None or not s.attrs or "rid" not in s.attrs:
            continue
        rid = s.attrs["rid"]
        host[rid] = host.get(rid, 0.0) + sign * s.dur_s
        if s.name == "serve.admit":
            admitted.add(rid)
    ms = [host[rid] * 1e3 for rid in admitted]
    return statistics.median(ms) if ms else None
