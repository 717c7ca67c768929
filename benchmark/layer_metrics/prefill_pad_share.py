"""Share of the prefill programs' token positions that were padding, %:
1 - sum(``tokens``) / sum(``bucket``) over the window's ``serve.prefill``
spans (``tokens`` is the real tail length the bucket was chosen for).
None where the spans carry no ``tokens`` attr."""


def read(record):
    fills = [s.attrs for s in record.get("spans", [])
             if s.name == "serve.prefill" and s.attrs
             and "tokens" in s.attrs]
    padded = sum(a["bucket"] for a in fills)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(a["tokens"] for a in fills) / padded)
