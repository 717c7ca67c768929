"""Share of the traced window, %, in which a collective runs on device 0
and no compute does (all-gather, reduce-scatter, all-reduce... ops and
their async spans, minus the union of every other leaf op)."""


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
