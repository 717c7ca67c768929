"""From a profiler trace (``.xplane.pb``) to numbers.

Two stages, so the arithmetic can be checked without a chip:

``extract(path)`` reads the XSpace with ``jax.profiler.ProfileData`` and
keeps plain lists: per TPU device the events of the ``XLA Ops``, ``XLA
Modules`` and ``Async XLA Ops`` lines, and from the host plane the named
annotations (``jax.profiler.TraceAnnotation``, which the program's tracer
mirrors its spans into while a capture runs; Python-call events, whose names
start with ``$``, are dropped).  Host and device events share one clock.
``lib/recorded_trace.json`` is such an extract, cut from a real v5e trace.

``reduce(extract)`` computes: device busy time (union of leaf-op intervals;
a ``while``/``call`` event contains its body's events and is not a leaf),
the traced window, time per op and per module, device time of the programs
launched inside a named host span, the flash (Mosaic) kernels' time, the
part of collective time during which no compute runs, and the idle gaps
named by the host spans open across them.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
# the program's spans are called <subsystem>.<section>: train.step,
# serve.prefix_match, ckpt.save; the runtime's own annotations are not
_PROGRAM_SPAN = re.compile(r"^[a-z_]+\.[a-z_]+$")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
MOSAIC_TARGET = "tpu_custom_call"


def parse_hlo(text: str) -> Dict[str, str]:
    """``%fusion.641 = bf16[4,2048,2048]{...} fusion(...)`` -> the op's
    name, its opcode, the head of its result type, a custom call's target."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest) if rest else None
    t = _TARGET.search(rest) if rest else None
    result = rest.split("{", 1)[0].strip("( ") if rest else ""
    return {"name": name.strip().lstrip("%"),
            "opcode": m.group(1) if m else "",
            "result": result[:40],
            "target": t.group(1) if t else ""}


def extract(path: str) -> Dict[str, Any]:
    """Plain lists from an ``.xplane.pb``.  Only the first TPU device's ops
    are parsed by name; of the others only the ``XLA Ops`` intervals are
    kept (their busy time is all that is read)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": []}
    planes = {int(m.group(1)): p for p in data.planes
              for m in [_DEVICE.match(p.name)] if m}
    for idx, plane in sorted(planes.items()):
        dev: Dict[str, List] = {"ops": [], "modules": [], "async": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules",
                   ASYNC_LINE: "async"}.get(line.name)
            if key is None or (idx != min(planes) and key != "ops"):
                continue
            for e in line.events:
                if idx != min(planes):
                    dev[key].append([e.start_ns, e.duration_ns])
                elif key == "modules":
                    dev[key].append([e.start_ns, e.duration_ns,
                                     e.name.split("(")[0]])
                else:
                    h = parse_hlo(e.name)
                    dev[key].append([e.start_ns, e.duration_ns, h["name"],
                                     h["opcode"], h["result"], h["target"]])
        out["devices"][str(idx)] = dev
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith("$") and e.duration_ns > 0:
                    out["host"].append([e.start_ns, e.duration_ns, e.name,
                                        line.name])
    return out


# ---- interval arithmetic ----------------------------------------------------

def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the (merged) intervals ``a`` not covered by (merged) ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def leaves(ops: Sequence[Sequence]) -> List[Sequence]:
    """Events that contain no other event of the line (a ``while`` or a
    ``call`` spans its body's events and is dropped)."""
    order = sorted(ops, key=lambda e: (e[0], -e[1]))
    is_parent = [False] * len(order)
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]][0] + order[stack[-1]][1] <= e[0]:
            stack.pop()
        if stack and e[0] + e[1] <= (order[stack[-1]][0]
                                     + order[stack[-1]][1]) and e[1] > 0:
            is_parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(order, is_parent) if not p]


def device_busy_s(ops: Sequence[Sequence]) -> float:
    """Seconds in which a leaf op ran on one device."""
    return length(union([(e[0], e[0] + e[1]) for e in leaves(ops)
                         if e[1] > 0])) * 1e-9


def is_collective(opcode: str, name: str = "") -> bool:
    """An all-gather, reduce-scatter... op or its -start/-done half, or one
    of XLA's ``async-collective-start/done`` wrappers (a fusion by opcode)."""
    return (any(opcode.startswith(c) for c in COLLECTIVES)
            or name.startswith("async-collective"))


def _span_stack(host: Sequence[Sequence], t: float) -> str:
    """Names of the main-thread host annotations open at time ``t``,
    outermost first."""
    open_ = [(h[0], -h[1], h[2]) for h in host
             if h[0] <= t < h[0] + h[1]]
    return ">".join(name for _, _, name in sorted(open_)) or "(no span)"


def reduce(ex: Dict[str, Any], device: str = "0") -> Dict[str, Any]:
    dev = ex["devices"][device]
    host = ex["host"]
    leaf = leaves(dev["ops"])
    busy = union([(e[0], e[0] + e[1]) for e in leaf if e[1] > 0])
    spans = [h for h in host if _PROGRAM_SPAN.match(h[2])]
    starts = [e[0] for e in dev["ops"]] + [h[0] for h in spans]
    ends = [e[0] + e[1] for e in dev["ops"]] + [h[0] + h[1] for h in spans]
    t0, t1 = min(starts), max(ends)

    # time per leaf op, keyed by module (the module whose interval holds it)
    # a module is labelled with the innermost host span it was launched in:
    # the serving programs are all called jit_prog
    mods = sorted(dev["modules"])
    mod_label = [f"{m[2]}@{_span_stack(spans, m[0]).rsplit('>', 1)[-1]}"
                 for m in mods]
    per_op: Dict[str, float] = {}
    mosaic_s, mosaic_n = 0.0, 0
    j = 0
    for e in sorted(leaf):
        while j + 1 < len(mods) and mods[j + 1][0] <= e[0]:
            j += 1
        module = mod_label[j] if mods and mods[j][0] <= e[0] else "?"
        label = f"{module}:{e[2]} {e[3]} {e[4]}"
        per_op[label] = per_op.get(label, 0.0) + e[1] * 1e-9
        if e[5] == MOSAIC_TARGET:
            mosaic_s += e[1] * 1e-9
            mosaic_n += 1

    # collectives: the async spans of collective starts, and synchronous
    # collective ops; exposed = collective time with no other leaf running
    coll = union([(e[0], e[0] + e[1]) for e in list(dev["async"]) + leaf
                  if is_collective(e[3], e[2]) and e[1] > 0])
    compute = union([(e[0], e[0] + e[1]) for e in leaf
                     if not is_collective(e[3], e[2]) and e[1] > 0])
    exposed = subtract(coll, compute)

    # idle gaps, named by the host spans open at their middle
    gaps: Dict[str, float] = {}
    edges = [(t0, t0)] + busy + [(t1, t1)]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            name = _span_stack(spans, (a + b) / 2)
            gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9

    return {
        "busy_s": length(busy) * 1e-9,
        "busy_s_device0": length(busy) * 1e-9,
        "window_s": (t1 - t0) * 1e-9,
        "per_op_s": per_op,
        "modules": [[m[0], m[1], m[2]] for m in mods],
        "host": [[h[0], h[1], h[2]] for h in spans],
        "mosaic_s": mosaic_s, "mosaic_invocations": mosaic_n,
        "collective_s": length(coll) * 1e-9,
        "collective_exposed_s": length(exposed) * 1e-9,
        "idle_gaps_s": gaps,
        "n_devices": len(ex["devices"]),
    }


def program_ms_in_span(tr: Dict[str, Any], span_name: str) -> List[float]:
    """Device milliseconds of the programs launched inside each host span
    called ``span_name`` (one entry per span that launched any)."""
    out = []
    mods = tr["modules"]
    for s0, dur, name in tr["host"]:
        if name != span_name:
            continue
        inside = [m[1] for m in mods if s0 <= m[0] < s0 + dur]
        if inside:
            out.append(sum(inside) * 1e-6)
    return out


def breakdown(tr: Dict[str, Any], top: int = 10) -> Dict[str, List]:
    """The contract's ``breakdown``: the device operations that took most
    time and the longest idle gaps by what the host was doing."""
    def rank(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": rank(tr["per_op_s"]),
            "idle_gaps": rank(tr["idle_gaps_s"])}


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def reduce_dir(trace_dir: str) -> Optional[Dict[str, Any]]:
    """Reduce the newest trace under ``trace_dir``; None when the profiler
    wrote nothing or no TPU device plane is in it."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    ex = extract(path)
    if not ex["devices"]:
        return None
    first = min(ex["devices"], key=int)
    out = reduce(ex, first)
    # the contract's busy_s is the mean over the chips used; every other
    # number is the first device's
    busy = [out["busy_s"]] + [device_busy_s(d["ops"])
                              for k, d in ex["devices"].items() if k != first]
    out["busy_s"] = sum(busy) / len(busy)
    return out
