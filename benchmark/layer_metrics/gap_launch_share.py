"""Share of the traced window, %, that is the *launch* part of the gaps
between two device programs (gaps under ``serve.idle`` left out):
from the call of the next program's ``serve.launch`` to that
program's start on the device: the uploads, the enqueue and the runtime's
turn-around (all of a gap before a program that was already enqueued).
With its two siblings it sums to the idle time between programs
(``lib/gap_anatomy.py``: the parts, and the clock tie their split rests
on).  None where the trace has no TPU plane, the program has no
``serve.launch`` span, or the launches do not fit the modules in
device order (one program the profiler lost is stepped over)."""
from benchmark.lib import gap_anatomy


def read(record):
    return gap_anatomy.gap_share(record, "launch")
