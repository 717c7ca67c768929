"""Bytes a decode tick's expert layers must move (each E layer's router,
its two latent projections and its shared expert once, and the two matrices
of every held expert a live row reached) over all the bytes the tick must
move (those + the M layers' weights and every live slot's state twice + the
* layers' weights and live K/V rows + the head), %, summed over the window's
``serve.decode`` spans (``lib/latent_moe_work.py``): how much of a tick's
traffic the experts in the latent are.  None where the model is another or
the spans carry no such attrs."""
from benchmark.lib import latent_moe_work


def read(record):
    calls = latent_moe_work.decode_calls(record)
    if not calls:
        return None
    cfg = record["serve"]["cfg"]
    work = [latent_moe_work.tick_bytes(cfg, a) for a in calls]
    return 100.0 * sum(w["expert_layer_bytes"] for w in work) / sum(
        sum(w.values()) for w in work)
