"""Share of the traced window, %, in which the device sat idle and the
engine was NOT asleep waiting for the next arrival: the idle gaps whose
innermost host span is anything but ``serve.idle`` (a gap under no span
counts: unnamed host time is host-bound until a span says otherwise).
``device_idle_share.serve`` minus this is "no work offered".

None where the program has no such spans (one that predates ``serve.idle``
would read its whole idle share here)."""


def read(record):
    tr = record["trace"]
    if tr is None or not any(h[2] == "serve.emit" for h in tr["host"]):
        return None
    held = sum(s for stack, s in tr["idle_gaps_s"].items()
               if stack.rsplit(">", 1)[-1] != "serve.idle")
    return 100.0 * held / tr["window_s"]
