"""Device time of the expert matmuls over the decode program's device time,
%, over the traced ticks.  The ops are the ones ``lax.ragged_dot`` compiles
to on the chip: the Mosaic custom calls ``ragged-dot-none*`` (three a layer)
and the ``ragged-dot-metadata`` they share (``lib/moe_work.py``).  None where
the decode program holds none."""
from benchmark.lib import moe_work


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    matmul_s = moe_work.expert_matmul_device_s(tr)
    program_s, _ = moe_work.program_device_s(tr)
    if not matmul_s or not program_s:
        return None
    return 100.0 * matmul_s / program_s
