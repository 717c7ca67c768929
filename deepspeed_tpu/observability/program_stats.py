"""Per-program device-time accounting: FLOPs, invocation counts, MFU.

The reference stack's flops profiler (``deepspeed/profiling``) is a
one-shot report around a configured step.  Serving has no "the step": a
serving engine's steady state is an INVENTORY of fixed-shape programs
(decode, one prefill per bucket, COW, tier movers, draft/verify under
speculation), each invoked at its own cadence — so accounting must be
per-program and live.  :class:`ProgramCatalog` is that ledger:

- **Compile-time cost**: when a program is first invoked, its FLOPs/bytes
  are read from ``jitted.lower(*args).cost_analysis()`` — the pre-backend
  HLO analysis, which costs NO extra backend compile (the lowering hits
  jax's tracing cache for the avals the call is about to use) and no
  device work.  One registration per program, at the same moment the
  program itself first compiles — the zero-recompile steady state never
  sees it.
- **Invocation counts**: one dict increment per program call (~the cost of
  a disabled trace_span), so ``flops * invocations`` is a live executed-
  FLOPs ledger per program and per engine.
- **Sampled synced wall time** (``sample_every=N``, default 0 = off):
  every Nth invocation of a program is timed through
  ``block_until_ready`` — a real device-time sample.  Off by default
  because a sync point breaks the async dispatch pipelining the serving
  tick and train step rely on; N picks the perturbation/coverage
  trade-off (N=100 ⇒ 1% of ticks pay a sync).  With samples,
  ``device_seconds_total`` per program and whole-engine MFU/roofline
  estimates become available (``mfu(peak_flops_per_s)``).

Exported surfaces (docs/OBSERVABILITY.md "Per-program accounting"):
``ServingEngine.program_stats()`` / ``health()["program_stats"]``, the
``serve/program_flops{program=...}`` / ``serve/device_seconds_total``
gauges, and the train engine's ``train/tflops_est`` / ``train/mfu_est``.

Every registration is guarded: a cost-analysis failure records zeros and
moves on — accounting never gates the program it is counting.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..utils.logging import logger

__all__ = ["ProgramCatalog", "account", "finish_sample",
           "peak_flops_per_sec", "PEAK_TFLOPS_ENV"]

PEAK_TFLOPS_ENV = "DS_TPU_PEAK_TFLOPS"


def peak_flops_per_sec() -> Optional[float]:
    """The chip's peak flops/s for MFU denominators, or ``None`` when
    unknown.  There is no baked-in spec-sheet table here (the benchmark
    keeps the published peaks, ``benchmark/lib/peaks.json``): the operator
    states the roof they trust via ``DS_TPU_PEAK_TFLOPS``."""
    raw = os.environ.get(PEAK_TFLOPS_ENV, "").strip()
    if not raw:
        return None
    try:
        v = float(raw)
    except ValueError:
        logger.warning("ignoring malformed $%s=%r (want TFLOP/s as a "
                       "number)", PEAK_TFLOPS_ENV, raw)
        return None
    return v * 1e12 if v > 0 else None


class _Stat:
    __slots__ = ("flops", "bytes", "invocations", "synced_samples",
                 "synced_seconds", "registered", "notes")

    def __init__(self):
        self.flops = 0.0          # per invocation, from cost_analysis
        self.bytes = 0.0
        self.invocations = 0
        self.synced_samples = 0
        self.synced_seconds = 0.0
        self.registered = False
        self.notes: Dict[str, Any] = {}   # how the program came to be


class ProgramCatalog:
    """Ledger of per-program cost + usage for one engine's inventory.

    Call pattern at a program's call site (see ``MeshExecutor.decode``)::

        if not catalog.known("decode"):
            catalog.register_call("decode", prog, *args)   # once, cheap
        t0 = catalog.invoke("decode")                      # count (+ sample?)
        out = prog(*args)
        if t0 is not None:                                 # sampled sync
            jax.block_until_ready(out)
            catalog.record_sync("decode", time.perf_counter() - t0)
    """

    def __init__(self, sample_every: int = 0):
        if int(sample_every) < 0:
            raise ValueError(f"sample_every={sample_every} must be >= 0 "
                             "(0 disables synced sampling)")
        self.sample_every = int(sample_every)
        self._stats: Dict[str, _Stat] = {}
        self._lock = threading.Lock()

    # ---------------------------------------------------------- registration

    def known(self, name: str) -> bool:
        st = self._stats.get(name)
        return st is not None and st.registered

    def register(self, name: str, flops: float = 0.0,
                 bytes: float = 0.0) -> None:
        """Record a program's per-invocation cost directly (the train
        engine registers its fused step from its own cost analysis)."""
        with self._lock:
            st = self._stats.setdefault(name, _Stat())
            st.flops = float(flops)
            st.bytes = float(bytes)
            st.registered = True

    def register_call(self, name: str, jitted: Any, *args: Any) -> None:
        """Cost-analyze ``jitted`` for the avals of ``args`` (the exact
        call about to run) and register the result.  Uses
        ``lower().cost_analysis()`` — the UNOPTIMIZED-HLO analysis, which
        triggers no backend compile and no device work; the lowering
        itself hits the jit tracing cache.  Failures register zeros so
        the attempt is never repeated per call."""
        flops = by = 0.0
        try:
            ca = jitted.lower(*args).cost_analysis()
            flops = float((ca or {}).get("flops", 0.0) or 0.0)
            by = float((ca or {}).get("bytes accessed", 0.0) or 0.0)
        except Exception as e:   # accounting never gates the program
            logger.warning("program_stats: cost analysis of %r failed "
                           "(%s: %s); registering zero cost", name,
                           type(e).__name__, e)
        self.register(name, flops=flops, bytes=by)

    def annotate(self, name: str, **notes: Any) -> None:
        """Facts about how ``name`` was built that its numbers do not show
        (the checkpoint policy the train engine resolved for its step):
        carried on the program's row of :meth:`table`."""
        with self._lock:
            self._stats.setdefault(name, _Stat()).notes.update(notes)

    # ------------------------------------------------------------ accounting

    def invoke(self, name: str, n: int = 1) -> Optional[float]:
        """Count one dispatch of ``name`` (``n`` program invocations — a
        speculative tick runs the draft program k times).  Returns a
        ``perf_counter`` start stamp when THIS dispatch should be
        synced-sampled (every ``sample_every``-th), else ``None`` — the
        common N=0 path is one increment under the lock, no clock read."""
        with self._lock:
            st = self._stats.setdefault(name, _Stat())
            st.invocations += n
            if self.sample_every and st.invocations % self.sample_every == 0:
                return time.perf_counter()
        return None

    def record_sync(self, name: str, dur_s: float) -> None:
        with self._lock:
            st = self._stats.setdefault(name, _Stat())
            st.synced_samples += 1
            st.synced_seconds += float(dur_s)

    def flops_of(self, name: str) -> float:
        """Registered per-invocation FLOPs of one program (0.0 when the
        cost analysis failed or the program is unknown)."""
        with self._lock:
            st = self._stats.get(name)
            return st.flops if st is not None else 0.0

    # -------------------------------------------------------------- reading

    def table(self) -> Dict[str, Dict[str, Any]]:
        """Per-program snapshot: per-invocation cost, usage counts, the
        executed-FLOPs ledger, and — when synced samples exist — the mean
        sampled wall time, estimated total device seconds
        (``invocations * mean``) and the achieved flops/s it implies."""
        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            items = [(k, (v.flops, v.bytes, v.invocations, v.synced_samples,
                          v.synced_seconds, dict(v.notes)))
                     for k, v in self._stats.items()]
        for name, (flops, by, inv, ns, secs, notes) in sorted(items):
            row: Dict[str, Any] = {
                **notes,
                "flops": flops,
                "bytes": by,
                "invocations": inv,
                "flops_total": flops * inv,
                "synced_samples": ns,
            }
            if ns:
                mean = secs / ns
                row["sampled_mean_s"] = mean
                row["device_seconds_est"] = mean * inv
                row["achieved_flops_per_s"] = (flops / mean if mean > 0
                                               else 0.0)
            else:
                row["device_seconds_est"] = 0.0
            out[name] = row
        return out

    def gauge_rows(self) -> List[Tuple[str, float, float]]:
        """Per-program ``(name, flops_total, device_seconds_est)`` for the
        per-tick gauge writer — a flat tuple list under one lock hold, no
        sort and no row dicts (``table()`` is the human/health surface;
        this runs in the serving loop every working tick)."""
        with self._lock:
            return [(name,
                     st.flops * st.invocations,
                     (st.synced_seconds / st.synced_samples
                      * st.invocations) if st.synced_samples else 0.0)
                    for name, st in self._stats.items()]

    def totals(self) -> Dict[str, float]:
        """Whole-engine rollup of the executed-FLOPs ledger and the
        device-seconds estimate (0.0 until synced samples exist)."""
        flops_total = device_s = 0.0
        sampled = True
        with self._lock:
            for st in self._stats.values():
                flops_total += st.flops * st.invocations
                if st.synced_samples:
                    device_s += (st.synced_seconds / st.synced_samples
                                 * st.invocations)
                elif st.invocations:
                    sampled = False
        return {"flops_total": flops_total,
                "device_seconds_est": device_s,
                "fully_sampled": sampled}

    def mfu(self, peak_flops_per_s: Optional[float] = None
            ) -> Optional[float]:
        """Whole-engine MFU estimate: executed FLOPs over estimated device
        seconds, against ``peak_flops_per_s`` (default: the operator's
        ``DS_TPU_PEAK_TFLOPS``).  ``None`` until every invoked program has
        synced samples AND a peak is known — a partial denominator would
        overstate utilization, and a spec-sheet default would fake it."""
        if peak_flops_per_s is None:
            peak_flops_per_s = peak_flops_per_sec()
        if not peak_flops_per_s:
            return None
        t = self.totals()
        if not t["fully_sampled"] or t["device_seconds_est"] <= 0:
            return None
        return (t["flops_total"] / t["device_seconds_est"]
                / peak_flops_per_s)


# -------------------------------------------------- call-site helpers
#
# The one register-on-first-sight + count (+ maybe-sample) protocol every
# program call site follows, None-safe so callers without a catalog pay a
# single comparison.  MeshExecutor, SpeculativeDecoder and the train
# engine all route through these — the protocol lives in ONE place.

def account(catalog: Optional[ProgramCatalog], name: str, prog: Any,
            args: tuple, n: int = 1) -> Optional[float]:
    """Register ``prog``'s lowered cost on first sight (no backend
    compile — the lowering hits the jit tracing cache for the exact avals
    the call is about to use) and count the dispatch.  Returns a
    ``perf_counter`` start stamp when this dispatch was picked for synced
    sampling, else ``None``."""
    if catalog is None:
        return None
    if not catalog.known(name):
        catalog.register_call(name, prog, *args)
    return catalog.invoke(name, n)


def finish_sample(catalog: ProgramCatalog, name: str, out: Any,
                  t0: float) -> None:
    """Close a sampled dispatch: block until ``out`` is ready and record
    the true device wall time.  A poisoned output is the caller's problem
    — the sample is simply dropped."""
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:
        return
    catalog.record_sync(name, time.perf_counter() - t0)
