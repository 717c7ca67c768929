"""Bytes of live latent cache rows over all the bytes a decode tick of a
latent-attention model must move (those rows + the weights it streams), %,
summed over the window's ``serve.decode`` spans: how much of a tick's
traffic the mechanism is.  Latent bytes: ``live_rows`` x the row's width x
layers; weight bytes: what lies outside the routed experts once +
``moe_experts_touched`` experts' matrices (``lib/mla_work.py``).  None where
the model has no latent cache or the spans carry no such attrs."""
from benchmark.lib import mla_work


def read(record):
    calls = mla_work.decode_calls(record)
    if not calls:
        return None
    cfg = record["serve"]["cfg"]
    work = [mla_work.tick_work(cfg, a) for a in calls]
    latent = sum(w["latent_bytes"] for w in work)
    return 100.0 * latent / (latent + sum(w["weight_bytes"] for w in work))
