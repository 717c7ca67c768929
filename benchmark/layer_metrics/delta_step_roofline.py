"""What the gated-delta-rule step itself must move over the traced decode
ticks (every live slot's matrix states of every delta layer once in and once
out, unpadded: ``lib/delta_work.py``) at the chip's published bandwidth, over
the device time of the kernel's ops (``delta_step.*``) in the decode program,
%.  Slots: ``state_slots`` of the first N ``serve.decode`` spans of the
window, N the decode programs in the trace (the capture starts with the
window).  None where the model is another, the tick holds no such kernel (the
plain step fuses into the compiler's own ops), or there is no device trace."""
from benchmark.lib import delta_work, flops, moe_work


def read(record):
    tr = record["trace"]
    calls = delta_work.decode_calls(record)
    if tr is None or not calls:
        return None
    step_s = delta_work.step_device_s(tr)
    _, n = moe_work.program_device_s(tr)
    if not step_s or not n:
        return None
    cfg = record["serve"]["cfg"]
    nbytes = sum(delta_work.step_bytes(cfg, a["state_slots"])
                 for a in calls[:n])
    bw = flops.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (nbytes / bw) / step_s
