"""Bytes of recurrent state read and written over all the bytes a decode
tick of a model with state-space layers must move (the state of every live
slot twice + the live K/V rows + the weights it streams), %, summed over the
window's ``serve.decode`` spans: how much of a tick's traffic the mechanism
is.  State bytes: ``state_slots`` x a slot's state and tail x layers x 2;
K/V bytes: ``live_rows`` x a row's width x layers (``lib/ssm_work.py``).
None where the model has no state-space layers or the spans carry no such
attrs."""
from benchmark.lib import ssm_work


def read(record):
    calls = ssm_work.decode_calls(record)
    if not calls:
        return None
    cfg = record["serve"]["cfg"]
    work = [ssm_work.tick_work(cfg, a) for a in calls]
    state = sum(w["state_bytes"] for w in work)
    return 100.0 * state / (state + sum(w["kv_bytes"] + w["weight_bytes"]
                                        for w in work))
