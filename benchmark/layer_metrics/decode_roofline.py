"""What a decode tick must move over HBM (the weights once, plus the K/V of
the tokens its active slots hold) at the chip's published bandwidth, over
the decode program's median device time, %.

Live tokens per tick come from the request stamps: at the start of each
``serve.decode`` span, every request between its first and last token holds
its prompt plus the tokens emitted so far (placed evenly between the two
stamps).  The mean over the window's decode ticks is used."""
import statistics

from benchmark.lib import flops, trace_reduce


def live_tokens(results, t: float) -> float:
    total = 0.0
    for r in results:
        n = len(r.output_ids)
        if n < 2 or not (r.first_token_s <= t < r.finish_s):
            continue
        done = (t - r.first_token_s) / (r.finish_s - r.first_token_s)
        total += len(r.input_ids) + 1 + (n - 1) * done
    return total


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    ms = trace_reduce.program_ms_in_span(tr, "serve.decode")
    ticks = [s.t0 for s in record.get("spans", []) if s.name == "serve.decode"]
    if not ms or not ticks:
        return None
    serve = record["serve"]
    live = statistics.fmean(live_tokens(serve["results"], t) for t in ticks)
    need = flops.decode_tick_bytes(serve["cfg"], live)
    bw = flops.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (statistics.median(ms) * 1e-3)
