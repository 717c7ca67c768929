"""``lib/gap_anatomy.py`` and its four readers (ISSUE 34) on hand-built
``modules`` / ``host`` / ``spans`` with a known offset between the device
plane and the host plane: the tie recovers it inside its bounds, a gap's
three parts sum to it, and every input a reader may meet that holds nothing
to read gives None without raising."""
import json
import os
import types

import pytest

from benchmark import run as bench_run
from benchmark.lib import gap_anatomy

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAMES = ["gap_fetch_share", "gap_host_share", "gap_launch_share",
         "host_busy_share"]
OFFSET = 1_400_000          # ns: device timestamps lead the host plane's
FIRST_SEQ = 17              # the warm-up launched before the window
WINDOW_S = 1e-4


def _read(name, record):
    return bench_run._load_reader(name)(record)


def _span(name, t0, dur_s, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, dur_s=dur_s,
                                 attrs=attrs or None)


# One engine's window on the HOST plane's clock, ns: (program, launch span,
# module, fetch span or None).  The smallest launch latency (program 5:
# 100) and the smallest fetch latency (program 4: 100) are equal, so the
# tie's midpoint is the true offset and the parts below are exact.
PROGRAMS = [
    ("prefill_256", (1_000, 1_400), (1_500, 6_500), (1_450, 6_700)),
    ("decode", (7_000, 7_300), (7_450, 10_450), (7_350, 10_600)),
    # launched ahead, before program 1 was fetched: runs right behind it
    ("decode", (7_300, 7_340), (10_460, 13_460), (10_700, 13_700)),
    # its fetch opens after it has ended: the host was not waiting
    ("decode", (13_800, 14_000), (14_300, 15_300), (16_000, 16_050)),
    ("decode", (16_100, 16_300), (16_600, 17_600), (16_350, 17_700)),
    # after a sleep until the next arrival
    ("decode", (30_100, 30_300), (30_200, 31_200), (30_350, 31_600)),
]
IDLE = (17_800, 30_000)
# gap 0>1: fetch 200 host 300 launch 450; 1>2: launch 10 (already enqueued);
# 2>3: 240, 100, 500; 3>4: 0, 800, 500; 4>5 lies under serve.idle
WANT_NS = {"fetch": 440, "host": 1_200, "launch": 1_460}


def _record(programs=PROGRAMS, offset=OFFSET, extra_modules=(), idle=IDLE):
    modules, host, spans = [], [], []
    for k, (prog, launch, module, fetch) in enumerate(programs):
        modules.append([module[0] - offset, module[1] - module[0],
                        "jit_serve_" + prog])
        host.append([launch[0], launch[1] - launch[0], "serve.launch"])
        spans.append(_span("serve.launch", launch[0] * 1e-9,
                           (launch[1] - launch[0]) * 1e-9, program=prog,
                           seq=FIRST_SEQ + k, ahead=0))
        if fetch is not None:
            host.append([fetch[0], fetch[1] - fetch[0], "serve.fetch"])
            spans.append(_span("serve.fetch", fetch[0] * 1e-9,
                               (fetch[1] - fetch[0]) * 1e-9, program=prog,
                               seq=FIRST_SEQ + k))
    for start, end, name in extra_modules:
        modules.append([start - offset, end - start, name])
    if idle:
        host.append([idle[0], idle[1] - idle[0], "serve.idle"])
    host.append([900, 40_000, "serve.tick"])
    return {"trace": {"modules": modules, "host": host,
                      "window_s": WINDOW_S},
            "spans": spans}


def test_the_tie_recovers_the_offset_inside_its_bounds(capsys):
    got = gap_anatomy.anatomy(_record())
    assert got["lower_ms"] <= OFFSET * 1e-6 <= got["upper_ms"]
    assert got["offset_ms"] == pytest.approx(OFFSET * 1e-6)
    assert got["width_ms"] == pytest.approx(200e-6)
    assert (got["launches_tied"], got["fetches_tied"]) == (6, 6)
    # one note line a record, whichever reader asks first
    record = _record()
    for name in NAMES[:3]:
        _read(name, record)
    notes = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("note ")]
    assert len(notes) == 2      # this record's and the first call's
    assert "gap_anatomy" in json.loads(notes[-1][5:])


def test_three_parts_sum_to_each_gap_and_idle_gaps_are_left_out():
    record = _record()
    got = gap_anatomy.anatomy(record)
    assert got["gaps"] == 4                     # 4>5 is under serve.idle
    for part, ns in WANT_NS.items():
        assert got[part + "_s"] == pytest.approx(ns * 1e-9)
        assert _read(f"gap_{part}_share", record) == pytest.approx(
            100.0 * ns * 1e-9 / WINDOW_S)
    assert sum(WANT_NS.values()) == 950 + 10 + 840 + 1_300
    assert got["gap_median_ms"] == pytest.approx((840 + 950) / 2 * 1e-6)
    # without the sleep the last gap counts: fetch 100, then the host until
    # the launch's call, then 100 of launch
    woke = gap_anatomy.anatomy(_record(idle=None))
    assert woke["gaps"] == 5
    assert woke["fetch_s"] == pytest.approx((440 + 100) * 1e-9)
    assert woke["host_s"] == pytest.approx((1_200 + 12_400) * 1e-9)
    assert woke["launch_s"] == pytest.approx((1_460 + 100) * 1e-9)


def test_a_gap_with_no_fetch_open_books_to_host():
    """Program 3's fetch opens after the program has ended: nothing of the
    gap behind it is fetch."""
    programs = [PROGRAMS[3], PROGRAMS[4]]
    got = gap_anatomy.anatomy(_record(programs, idle=None))
    assert got["gaps"] == 1 and got["fetch_s"] == 0.0
    # two pairs tie more loosely than six: the split moves with the
    # midpoint, their sum and the whole do not
    assert got["host_s"] + got["launch_s"] == pytest.approx(1_300e-9)
    assert got["width_ms"] == pytest.approx((100 + 500) * 1e-6)


def test_a_module_of_no_launch_is_matched_to_nothing():
    """A page copy, or a scalar's conversion inside a launch, between two
    programs: no launch is tied to it, it does not end the gap, and its own
    time is taken out of the part it falls in."""
    copy = (6_800, 6_900, "jit_prog")               # while the host works
    convert = (7_100, 7_150, "jit_convert_element_type")  # inside launch 1
    got = gap_anatomy.anatomy(_record(extra_modules=[copy, convert]))
    assert got["launches_tied"] == 6 and got["gaps"] == 4
    assert got["fetch_s"] == pytest.approx(WANT_NS["fetch"] * 1e-9)
    assert got["host_s"] == pytest.approx((WANT_NS["host"] - 100) * 1e-9)
    assert got["launch_s"] == pytest.approx((WANT_NS["launch"] - 50) * 1e-9)
    # the launch calls that open inside a gap, host plane alone: 1, 3, 4
    assert got["launch_calls_s"] == pytest.approx((300 + 200 + 200) * 1e-9)


@pytest.mark.parametrize("lost,gaps,fetch_launch,host", [
    # the gaps on either side of the lost program are left out: what the
    # device plane shows between its neighbours is a hole in the trace
    (0, 3, 10 + 740 + 500, 100 + 800),
    (1, 2, 740 + 500, 100 + 800),
    (2, 2, 650 + 500, 300 + 800),
    (3, 2, 650 + 10, 300),
    (4, 3, 650 + 10 + 740, 300 + 100),
])
def test_a_program_the_profiler_lost_takes_no_launch(lost, gaps,
                                                     fetch_launch, host,
                                                     capsys):
    """A traced run of the Kanana cell came back one decode program short
    (PERF.md §6, PR 34).  Counted from the window's start every later
    launch would meet its neighbour's module; the pair at the hole
    contradicts the bounds of the pairs before it, so that launch is left
    without a module, the next one takes it, and the tie is the whole
    trace's."""
    record = _record()
    del record["trace"]["modules"][lost]
    got = gap_anatomy.anatomy(record)
    assert (got["launches_lost"], got["launches_tied"]) == (1, 5)
    assert f"launches [{lost}] have no module" in capsys.readouterr().err
    assert got["lower_ms"] <= OFFSET * 1e-6 <= got["upper_ms"]
    assert got["width_ms"] <= 250e-6
    assert got["gaps"] == gaps
    assert got["host_s"] == pytest.approx(host * 1e-9)
    assert got["fetch_s"] + got["launch_s"] == pytest.approx(
        fetch_launch * 1e-9)
    assert gap_anatomy.anatomy(_record())["launches_lost"] == 0


def test_crossed_bounds_say_so_and_give_none(capsys):
    # a fetch that returns before its program has ended on any clock that
    # also starts program 5 after its launch: no offset fits
    programs = list(PROGRAMS)
    programs[1] = ("decode", (7_000, 7_300), (7_450, 10_450), (7_350, 9_000))
    record = _record(programs)
    for name in NAMES[:3]:
        assert _read(name, record) is None
    assert "bounds cross" in capsys.readouterr().err     # at 4 launches of 6
    # a launch whose program is not the module's at its place in the order
    swapped = _record()
    swapped["trace"]["modules"][0][2] = "jit_serve_decode"
    assert _read("gap_host_share", swapped) is None
    assert "matching is wrong" in capsys.readouterr().err


def test_nothing_to_read_gives_none_without_raising():
    # the CPU rehearsal: no TPU plane
    cpu = dict(_record(), trace=None)
    # a program that predates the spans: modules and other spans only
    old = _record()
    old["trace"]["host"] = [h for h in old["trace"]["host"]
                            if h[2] == "serve.tick"]
    old["spans"] = [_span("serve.decode", 0.0, 0.01, tick=1)]
    # launches and no fetch at all: nothing bounds the offset from above
    unfetched = _record([p[:3] + (None,) for p in PROGRAMS])
    for record in (cpu, old, unfetched, {"trace": None}, {}):
        for name in NAMES[:3]:
            assert _read(name, record) is None
    assert _read("host_busy_share", old) is None
    assert _read("host_busy_share", {}) is None
    # the spans alone are enough for the host's own share
    assert _read("host_busy_share", cpu) is not None


def test_host_busy_share_over_the_whole_window():
    spans = [
        _span("serve.tick", 10.0, 1.0, tick=1),
        _span("serve.fetch", 10.1, 0.6, program="prefill_256", seq=3),
        _span("serve.idle", 11.0, 2.0, wait_s=2.0),
        # the tick in which the capture stops: 5 s inside stop_trace
        _span("serve.tick", 13.0, 6.0, tick=2),
        _span("profile.stop", 13.2, 5.0),
        _span("serve.fetch", 18.3, 0.4, program="decode", seq=4),
        _span("train.step", 0.0, 99.0),          # not the serving thread's
        # the drain: opened after the window's end
        _span("serve.tick", 19.5, 3.0, tick=3),
        _span("serve.fetch", 19.6, 2.9, program="decode", seq=5),
    ]
    # extent 10 > 19 = 9 s less the stop's 5 = 4; waiting 0.6 + 2 + 0.4 = 3
    window = {"spans": spans, "serve": {"t_end": 19.0}}
    assert _read("host_busy_share", window) == pytest.approx(25.0)
    # an open-loop record has no t_end: the whole run, 12.5 less 5 = 7.5 s,
    # 5.9 s of it waiting
    assert _read("host_busy_share", {"spans": spans, "serve": {}}) == \
        pytest.approx(100.0 * (1 - 5.9 / 7.5))


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells_that_serve(manifest):
    """The cells whose traffic file's ``kind`` starts with ``serve``."""
    cells = set()
    for cell in manifest["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            if json.load(f)["kind"].startswith("serve"):
                cells.add(cell["name"])
    return cells


def test_the_manifest_lists_the_four_for_the_serving_cells_alone(manifest):
    """A rule, not a count: every cell that serves reports the one capacity
    metric and the four readers, and no training cell does; a later PR adds
    a serving cell, or per-layer metrics of its own anywhere in the list,
    as entries alone (``test_room.py`` does both)."""
    (serving,) = [set(m["workloads"]) for m in manifest["end_to_end"]
                  if m["name"] == "serve_tokens_per_s"]
    assert serving == _cells_that_serve(manifest)
    assert {c["name"] for c in manifest["workloads"]} - serving   # training
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # all four, in this order among themselves
    assert [m["name"] for m in manifest["per_layer"]
            if m["name"] in NAMES] == NAMES
    for name in NAMES:
        m = by_name[name]
        assert set(m["workloads"]) == serving
        assert (m["layer"], m["moves"], m["better"], m["unit"]) == (
            "serving scheduler", "serve_tokens_per_s", "lower", "%")
        assert m["source"] == ("program_span" if name == "host_busy_share"
                               else "device_trace")


@pytest.mark.parametrize("cell,serving", [
    ("opt-1.3b.chat-backlog", True),
    ("pythia-1.4b-d10.zero1", False),
])
def test_rehearsal_names(cell, serving, capsys):
    """``--rehearse --trace 1``: a serving cell asks all four readers, and
    the one that needs no device plane reports (a CPU run yields no device
    metric, so the three gap shares are left out, not faked); a training
    cell asks none."""
    from deepspeed_tpu.observability import configure_tracer, get_tracer

    asked = []
    load = bench_run._load_reader

    def spy(name):
        asked.append(name)
        return load(name)

    bench_run._load_reader = spy
    try:
        rc = bench_run.main(["--workload", cell, "--seed", "3400000033",
                             "--seconds", "1.5", "--trace", "1",
                             "--rehearse"])
    finally:
        bench_run._load_reader = load
        configure_tracer(enabled=False)
        get_tracer().reset()
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("rehearsal ")]
    names = set(json.loads(lines[-1][len("rehearsal "):])["metric_names"])
    assert set(NAMES) & set(asked) == (set(NAMES) if serving else set())
    assert set(NAMES) & names == ({"host_busy_share"} if serving else set())
