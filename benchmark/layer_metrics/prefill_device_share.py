"""Device time of the prefill programs (``jit_serve_prefill_*``) over the
device's busy time in the traced window, %: how much of what the chip does
is prompts and not ticks.  None where there is no device trace or it holds
no prefill program."""
from benchmark.lib import ssm_moe_work


def read(record):
    tr = record["trace"]
    if tr is None or not tr["busy_s"]:
        return None
    prefill_s = ssm_moe_work.programs_device_s(
        tr, ssm_moe_work.PREFILL_PROGRAMS)
    return 100.0 * prefill_s / tr["busy_s"] if prefill_s else None
