"""Bytes of matrix state (and convolution tail) read and written over all
the bytes a decode tick must move (the state of every live slot twice over
the delta layers + the live K/V rows of the attention layers + the streamed
weights), %, summed over the window's ``serve.decode`` spans
(``lib/delta_work.py``): how much of a tick's traffic the mechanism is.  None
where the model is another or the spans carry no such attrs."""
from benchmark.lib import delta_work


def read(record):
    calls = delta_work.decode_calls(record)
    if not calls:
        return None
    cfg = record["serve"]["cfg"]
    work = [delta_work.tick_bytes(cfg, a) for a in calls]
    return 100.0 * sum(w["state_bytes"] for w in work) / sum(
        sum(w.values()) for w in work)
