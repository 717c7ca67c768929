"""What the idle time between two device programs of the serving engine is
made of, and how busy the serving thread itself is.

The program marks every call that enqueues a decode or prefill program with
a ``serve.launch`` span and every blocking read of a program's output with a
``serve.fetch`` span; both carry ``seq``, the engine's count of launches, so
that launch k is the k-th module named ``jit_serve_decode`` or
``jit_serve_prefill_<bucket>`` the device runs (a tick launched ahead and
dropped still runs and still counts; it only has no fetch) and a fetch
reads the module of its ``seq``.  Any other module (a page copy, a
speculative program) is matched to nothing and its time is not a gap.

**The clock tie.**  The profiler's device plane and its host plane do not
share a zero (PERF.md §6, PR 24).  A program cannot start before its launch
was called and a fetch cannot return before its program ended, so with
``offset`` = what is added to a device timestamp to put it on the host
plane's clock, every pair gives one inequality::

    offset >= launch_start - module_start        (every matched launch)
    offset <= fetch_end    - module_end          (every matched fetch)

The largest lower and the smallest upper bound pin the offset to the
smallest launch and fetch latencies of the capture; the midpoint is used and
the width (upper - lower) is carried.

**A program the profiler lost.**  The device plane can come back a program
short (one decode program of 93 in a traced run of the Kanana cell: 26 ms of
the plane empty while the host sat in a fetch, PERF.md §6 PR 34).  Counted
from the window's start, every launch behind the hole would then meet its
neighbour's module, and the pair at the hole contradicts the bounds of the
pairs before it by the length of a program (its fetch returned a program
before that module ended).  So the pairs are made in order, each held
against the bounds so far: a launch whose pair would cross them, or whose
program is not the module's, is left without a module (``launches_lost``,
named on stderr), the next launch takes that module, and a gap with such a
launch in it is left out: what the plane shows there is a hole in the
trace, not idle time.  More than a tenth of the launches (or two) left so
means the matching itself is wrong: said on stderr, and nothing is reported.

**A gap's three parts**, for every gap between two consecutive programs of
the engine over which no ``serve.idle`` span lies (the engine asleep until the next arrival:
no work offered), on the host plane's clock with ``a`` the first module's
end and ``b`` the second's start::

    launch  from the start of the second module's serve.launch (clamped into
            [a, b]: a program enqueued before the first one ended leaves a
            gap that is all launch, the runtime's own turn-around) to b
    fetch   from a to the end of the serve.fetch open at a, no further than
            the launch's start (0 where none was open: the host was not
            waiting)
    host    what lies between: the fetch's return to the launch's call

They sum to the gap.  ``host`` lies on the host plane alone; ``fetch +
launch`` = gap - ``host`` needs no offset; only their split moves with it,
by at most half the tie's width a gap.  A module of no launch between two
programs (``MeshExecutor.prefill``'s two ``jit_convert_element_type``
scalars run inside its launch) does not end the gap: its own time is taken
out of the part it falls in.  The ``note`` line also carries
``launch_calls_s``: the durations of the ``serve.launch`` spans that open
inside a gap, host plane alone: the part of ``fetch + launch`` that is the
host inside the launch call, whatever the offset.
"""
from __future__ import annotations

import bisect
import json
import statistics
import sys
from typing import Any, Dict, List, Optional

from benchmark.lib import trace_reduce

LAUNCH, FETCH, IDLE, STOP = ("serve.launch", "serve.fetch", "serve.idle",
                             "profile.stop")
MODULE_PREFIX = "jit_serve_"
_KEY = "_gap_anatomy"


def _spans(record, name: str) -> List[Any]:
    return sorted((s for s in record.get("spans", [])
                   if s.name == name and s.attrs and "seq" in s.attrs),
                  key=lambda s: s.t0)


def _events(tr, name: str) -> List[List[int]]:
    return sorted([h[0], h[0] + h[1]] for h in tr["host"] if h[2] == name)


def _is_engine_program(module_name: str) -> bool:
    return (module_name == MODULE_PREFIX + "decode"
            or module_name.startswith(MODULE_PREFIX + "prefill_"))


def _warn(text: str) -> None:
    print(f"gap_anatomy: {text}", file=sys.stderr, flush=True)


def anatomy(record) -> Optional[Dict[str, Any]]:
    """The tie and the gaps' three parts over the traced window, or None:
    no TPU plane, a program without the two spans, no pair to tie the
    clocks with, or launches that do not fit the modules in device order.
    Computed once a record, with one
    ``note`` line of the tie on stdout."""
    if _KEY not in record:
        record[_KEY] = _anatomy(record)
        if record[_KEY] is not None:
            print("note", json.dumps({"gap_anatomy": record[_KEY]}),
                  flush=True)
    return record[_KEY]


def _anatomy(record) -> Optional[Dict[str, Any]]:
    tr = record.get("trace")
    if tr is None:
        return None
    launches, fetches = _events(tr, LAUNCH), _events(tr, FETCH)
    launch_spans, fetch_spans = _spans(record, LAUNCH), _spans(record, FETCH)
    if not launches or not launch_spans:
        return None
    # the capture opens with the window: the i-th mirrored annotation of a
    # name is the i-th span of that name, and launch k runs as module k
    modules = sorted(tr["modules"])
    ours = [i for i, m in enumerate(modules) if _is_engine_program(m[2])]
    n = min(len(launches), len(launch_spans))
    first_seq = launch_spans[0].attrs["seq"]
    fetch_end = {span.attrs["seq"] - first_seq: f_end
                 for (_, f_end), span in zip(fetches, fetch_spans)}
    # ... unless the profiler lost a program: the pair at the hole crosses
    # the bounds of the pairs before it, that launch goes without a module
    # and the next one takes it
    pairs, lost = [], []
    lower, upper = float("-inf"), float("inf")
    for k in range(n):
        if len(pairs) == len(ours):
            break
        m = modules[ours[len(pairs)]]
        lo = max(lower, launches[k][0] - m[0])
        hi = (min(upper, fetch_end[k] - (m[0] + m[1])) if k in fetch_end
              else upper)
        want = MODULE_PREFIX + str(launch_spans[k].attrs.get("program"))
        if m[2] != want or lo > hi:
            lost.append(k)
            continue
        pairs.append((k, ours[len(pairs)]))
        lower, upper = lo, hi
    tied_fetches = sum(1 for k, _ in pairs if k in fetch_end)
    if not tied_fetches:
        return None
    if len(lost) > max(2, n // 10):
        _warn(f"the tie's bounds cross, or the program is another's, at "
              f"{len(lost)} of {n} launches (the first: launch {lost[0]}, "
              f"{launch_spans[lost[0]].attrs.get('program')}): the matching "
              "is wrong")
        return None
    if lost:
        _warn(f"launches {lost} have no module in the device plane: the "
              "profiler lost them; the gaps beside them are left out")
    offset = (lower + upper) / 2.0

    idle = _events(tr, IDLE)
    fetch_starts = [f[0] for f in fetches]
    parts = {"fetch": 0.0, "host": 0.0, "launch": 0.0}
    gaps, launch_calls = [], 0.0
    for (k0, i), (k, j) in zip(pairs, pairs[1:]):
        a = modules[i][0] + modules[i][1] + offset
        b = modules[j][0] + offset
        # a lost program ran in between: what the plane shows there is a
        # hole in the trace, not a gap
        if (k != k0 + 1 or b <= a
                or any(lo < b and hi > a for lo, hi in idle)):
            continue
        f = bisect.bisect_right(fetch_starts, a) - 1
        fetched_at = fetches[f][1] if f >= 0 and fetches[f][1] >= a else a
        launch_at = min(max(launches[k][0], a), b)
        cuts = [a, min(fetched_at, launch_at), launch_at, b]
        # a module of no launch in between (a page copy, a scalar's
        # conversion): its time is not a gap, whichever part it falls in
        others = [(m[0] + offset, m[0] + m[1] + offset)
                  for m in modules[i + 1:j]]
        gap = 0.0
        for part, lo, hi in zip(parts, cuts, cuts[1:]):
            idle_ns = trace_reduce.length(
                trace_reduce.subtract([(lo, hi)], others))
            parts[part] += idle_ns
            gap += idle_ns
        gaps.append(gap)
        if a < launch_at < b:
            # the launch call itself, on the host plane alone: what of
            # fetch + launch is the host inside serve.launch (uploads and
            # the enqueue), the rest being latency on either side
            launch_calls += launches[k][1] - launches[k][0]
    long_gaps = [g for g in gaps if g > upper - lower]
    return {
        "offset_ms": offset * 1e-6, "width_ms": (upper - lower) * 1e-6,
        "lower_ms": lower * 1e-6, "upper_ms": upper * 1e-6,
        "launches_tied": len(pairs), "fetches_tied": tied_fetches,
        "launches_lost": len(lost),
        "gaps": len(gaps),
        "gap_median_ms": statistics.median(gaps) * 1e-6 if gaps else None,
        # the gaps longer than the tie's width (the others are the
        # microsecond between two programs that were both enqueued)
        "long_gaps": len(long_gaps),
        "long_gap_median_ms": (statistics.median(long_gaps) * 1e-6
                               if long_gaps else None),
        "fetch_s": parts["fetch"] * 1e-9, "host_s": parts["host"] * 1e-9,
        "launch_s": parts["launch"] * 1e-9,
        "launch_calls_s": launch_calls * 1e-9,
        "window_s": tr["window_s"],
    }


def gap_share(record, part: str) -> Optional[float]:
    """One part (``fetch``, ``host``, ``launch``) summed over the traced
    window's gaps, % of the traced window."""
    got = anatomy(record)
    if got is None:
        return None
    return 100.0 * got[part + "_s"] / got["window_s"]


def host_busy_share(record) -> Optional[float]:
    """% of the serving thread's time, over the whole window, in which it
    neither waited for the device (``serve.fetch``) nor slept until the next
    arrival (``serve.idle``).  The extent is the first ``serve.*`` span's
    start to the last one's end among the spans opened inside the window
    (``serve["t_end"]`` where the record has it: a backlog's drain is left
    out); the seconds the profiler's stop blocked the thread
    (``profile.stop``) are taken out of both.  None where the program has no
    ``serve.fetch`` span."""
    t_end = record.get("serve", {}).get("t_end", float("inf"))
    spans = [s for s in record.get("spans", [])
             if s.t0 <= t_end and s.dur_s is not None]
    serve = [s for s in spans if s.name.startswith("serve.")]
    waiting = [s.dur_s for s in serve if s.name in (FETCH, IDLE)]
    if not any(s.name == FETCH for s in serve):
        return None
    stop = sum(s.dur_s for s in spans if s.name == STOP)
    extent = (max(s.t0 + s.dur_s for s in serve)
              - min(s.t0 for s in serve) - stop)
    if extent <= 0:
        return None
    return 100.0 * (1.0 - sum(waiting) / extent)
