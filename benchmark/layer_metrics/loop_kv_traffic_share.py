"""Bytes of keys and values over all the bytes a decode tick of a looped
model must move (the live rows of a cache ``passes x layers`` deep + the
stack once a pass + the head), %, summed over the window's ``serve.decode``
spans: how much of a tick is the pass-deep cache.  K/V bytes: the program's
``kv_bytes``; weights: ``lib/loop_work.py``.  None where the model is not
looped or the spans carry no such attrs."""
from benchmark.lib import loop_work


def read(record):
    calls = loop_work.decode_calls(record)
    if not calls:
        return None
    cfg = record["serve"]["cfg"]
    work = [loop_work.tick_work(cfg, a) for a in calls]
    kv = sum(w["kv_bytes"] for w in work)
    return 100.0 * kv / (kv + sum(w["weight_bytes"] for w in work))
