"""Mean number of slots decoding per working tick: the ``slot_rids`` map
each ``serve.tick`` span carries while the tracer is on."""


def read(record):
    counts = [len(s.attrs["slot_rids"]) for s in record.get("spans", [])
              if s.name == "serve.tick" and s.attrs
              and "slot_rids" in s.attrs]
    return sum(counts) / len(counts) if counts else None
