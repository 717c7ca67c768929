"""Bytes of recurrent state read and written over all the bytes a decode
tick must move (the state of every live slot twice over the mamba layers +
the live K/V rows of the attention layers + the dense weights + the held
experts touched), %, summed over the window's ``serve.decode`` spans
(``lib/ssm_moe_work.py``): how much of a tick's traffic the state layers
are.  None where the model is another or the spans carry no such attrs."""
from benchmark.lib import ssm_moe_work


def read(record):
    calls = ssm_moe_work.decode_calls(record)
    if not calls:
        return None
    cfg = record["serve"]["cfg"]
    work = [ssm_moe_work.tick_bytes(cfg, a) for a in calls]
    return 100.0 * sum(w["state_bytes"] for w in work) / sum(
        sum(w.values()) for w in work)
