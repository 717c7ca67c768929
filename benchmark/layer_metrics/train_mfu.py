"""Model-FLOP/s utilisation, %: lib/flops.py's operations per token times
the median-step tokens/s/chip over the chip's published bf16 peak.
Recomputed operations are not counted."""
from benchmark.lib import flops, stats


def read(record):
    if record["rehearse"]:
        return None
    t = record["train"]
    rate = stats.median_step_rate(t["step_s"], t["tokens_per_step"], t["chips"])
    return 100.0 * flops.mfu(rate, t["cfg"], t["seq_len"],
                             record["device"]["kind"])
