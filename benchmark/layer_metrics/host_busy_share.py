"""Share of the serving thread's time, %, over the whole window (a
backlog's drain left out), in which it neither waited for the device
(``serve.fetch``) nor slept until the next arrival (``serve.idle``), the
seconds of ``profile.stop`` taken out: how close the host is to setting
the pace, in the steady state the device capture never sees
(``lib/gap_anatomy.py``).  None where the program has no ``serve.fetch``
span."""
from benchmark.lib import gap_anatomy


def read(record):
    return gap_anatomy.host_busy_share(record)
