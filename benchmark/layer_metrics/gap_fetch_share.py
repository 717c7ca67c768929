"""Share of the traced window, %, that is the *fetch* part of the gaps
between two device programs (gaps under ``serve.idle`` left out):
from a program's end to the return of the ``serve.fetch`` that was
open at it: the host waiting for an output it has already asked for.
With its two siblings it sums to the idle time between programs
(``lib/gap_anatomy.py``: the parts, and the clock tie their split rests
on).  None where the trace has no TPU plane, the program has no
``serve.launch`` span, or the launches do not fit the modules in
device order (one program the profiler lost is stepped over)."""
from benchmark.lib import gap_anatomy


def read(record):
    return gap_anatomy.gap_share(record, "fetch")
