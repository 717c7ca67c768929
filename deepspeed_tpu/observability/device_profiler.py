"""Device-trace correlation: windowed XLA-profiler captures + span annotation.

The PR 4 tracer is deliberately host-side: it stamps host clocks around
device calls and (when synced) measures wall time, but it cannot say WHERE
inside a step the device spent its time — the ROADMAP names that the
missing tool for the MFU-reclaim work (BENCH_r05: MFU 0.613 against the
measured matmul roof, with no way to see where the missing third goes).

This module is the device half:

- :class:`DeviceTraceCapture` — a windowed capture manager around
  ``jax.profiler.start_trace`` / ``stop_trace``.  A capture is bounded
  either by an explicit unit budget (``n_units`` train steps / serving
  ticks — the loops call :func:`device_trace_unit` at each boundary) or by
  an explicit :func:`stop_device_trace`.  Unbounded always-on device
  tracing is not offered: XLA traces are huge and the profiler itself
  perturbs the run, so the tool is a WINDOW around the region under study.
- **Correlation**: while a capture is active, every ``trace_span`` ALSO
  enters a ``jax.profiler.TraceAnnotation`` of the same name, so the host
  spans (``train.step``, ``serve.decode``, ``serve.prefill``...) appear as
  named regions on the host timeline of the XLA/TensorBoard trace viewer,
  lined up against the device ops they dispatched.  The hook is installed
  only for the capture window (one module-global check per span when off),
  and works even when the HOST tracer is disabled — arming a device
  capture must not require also paying for host-side recording.

Opt-in surfaces:

- ``DS_TPU_DEVICE_TRACE=<dir>`` (+ optional ``DS_TPU_DEVICE_TRACE_UNITS``,
  default 16): the first train/serving engine init arms one capture of N
  units into ``<dir>`` — zero code changes to profile a production run's
  first N steps/ticks.
- ``capture_device_trace(log_dir, n_units=...)`` — the API that windows a
  capture around an extra measured pass (``benchmark/run.py --trace 1``:
  the reported pass stays untraced).

View with TensorBoard: ``tensorboard --logdir <dir>`` → Profile tab
(docs/OBSERVABILITY.md "Device-time correlation").  Every failure path
degrades to a warning: observability never gates the product.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Optional

from ..utils.logging import logger

DEVICE_TRACE_ENV = "DS_TPU_DEVICE_TRACE"
DEVICE_TRACE_UNITS_ENV = "DS_TPU_DEVICE_TRACE_UNITS"
DEFAULT_CAPTURE_UNITS = 16

__all__ = ["DeviceTraceCapture", "capture_device_trace",
           "device_capture_active", "device_trace_unit",
           "stop_device_trace", "maybe_capture_from_env",
           "DEVICE_TRACE_ENV", "DEVICE_TRACE_UNITS_ENV"]


def capture_options():
    """The ``ProfileOptions`` every capture runs with.  The Python tracer is
    off: JAX's default hooks every Python call of the traced loop for the
    length of the capture — events nobody reads, and a slower host in the
    very window under study (a serving tick's host time read 2.9 ms with it
    and 1.7 ms without, PERF.md §6 PR 24).  The host tracer is at 1, the
    lowest level that still records ``TraceAnnotation``: the mirrored spans
    are what the capture is for.  The device planes depend on neither
    level, and they, not Python, are what ``stop_trace`` spends its seconds
    on."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class DeviceTraceCapture:
    """One windowed XLA-profiler capture.  Constructed armed-and-started;
    :meth:`unit` counts down the window (``n_units=None`` = until an
    explicit :meth:`stop`).  ``annotations`` counts the span annotations
    emitted while active — the correlation smoke asserts it moves only
    inside the window."""

    def __init__(self, log_dir: str, n_units: Optional[int] = None):
        if n_units is not None and int(n_units) < 1:
            raise ValueError(f"n_units={n_units} must be >= 1 (or None "
                             "for an explicit stop)")
        self.log_dir = str(log_dir)
        self.remaining = int(n_units) if n_units is not None else None
        self.active = False
        self.failed: Optional[str] = None
        self.annotations = 0
        self._lock = threading.Lock()
        self._start()

    # ------------------------------------------------------------ lifecycle

    def _start(self) -> None:
        try:
            import jax.profiler

            os.makedirs(self.log_dir, exist_ok=True)
            jax.profiler.start_trace(self.log_dir,
                                     profiler_options=capture_options())
        except Exception as e:   # profiler unavailable / already tracing
            self.failed = f"{type(e).__name__}: {e}"
            logger.warning("device trace capture into %s failed to start "
                           "(%s); continuing without", self.log_dir, e)
            return
        self.active = True
        from . import trace as trace_mod

        trace_mod._set_device_annotation_factory(self._annotation)
        logger.info("device trace capture started into %s (%s)",
                    self.log_dir,
                    f"{self.remaining} units" if self.remaining is not None
                    else "until stopped")

    def _annotation(self, name: str) -> Any:
        """The factory ``trace_span`` calls while this capture is active:
        a ``jax.profiler.TraceAnnotation`` named like the host span."""
        import jax.profiler

        self.annotations += 1
        return jax.profiler.TraceAnnotation(name)

    def unit(self) -> None:
        """One step/tick boundary passed; stop when the window is spent."""
        if not self.active or self.remaining is None:
            return
        stop = False
        with self._lock:
            self.remaining -= 1
            if self.remaining <= 0:
                stop = True
        if stop:
            self.stop()

    def stop(self) -> Optional[str]:
        """Stop the capture and detach the span-annotation hook; returns
        the log dir (``None`` when the capture never started).  Idempotent
        — the unit countdown and an explicit stop may race benignly."""
        with self._lock:
            if not self.active:
                return None
            self.active = False
        from . import trace as trace_mod

        trace_mod._set_device_annotation_factory(None)
        try:
            import jax.profiler

            # the seconds the stop blocks its caller (the serving loop's
            # tick, a train step), as a host span of their own: a reader of
            # the whole window takes them out of the host's work
            with trace_mod.trace_span("profile.stop"):
                jax.profiler.stop_trace()
        except Exception as e:   # pragma: no cover - backend hiccup
            logger.warning("device trace stop failed (%s); trace under %s "
                           "may be incomplete", e, self.log_dir)
            return None
        logger.info("device trace capture written under %s (view: "
                    "tensorboard --logdir %s)", self.log_dir, self.log_dir)
        return self.log_dir


_CAPTURE: Optional[DeviceTraceCapture] = None
_ENV_ARMED = False


def capture_device_trace(log_dir: Optional[str] = None,
                         n_units: Optional[int] = None
                         ) -> Optional[DeviceTraceCapture]:
    """Arm-and-start a windowed device capture (the process-global one the
    train/serving loops count down).  ``log_dir`` defaults to
    ``$DS_TPU_DEVICE_TRACE``; ``n_units`` bounds the window in loop units
    (train steps / serving ticks), ``None`` means until
    :func:`stop_device_trace`.  A capture already running wins (the caller
    gets it back unchanged); a failed profiler start returns ``None``."""
    global _CAPTURE
    if _CAPTURE is not None and _CAPTURE.active:
        return _CAPTURE
    if log_dir is None:
        log_dir = os.environ.get(DEVICE_TRACE_ENV, "").strip()
        if not log_dir:
            raise ValueError(
                "capture_device_trace needs a log_dir (or set "
                f"${DEVICE_TRACE_ENV})")
    cap = DeviceTraceCapture(log_dir, n_units=n_units)
    if cap.failed is not None:
        return None
    _CAPTURE = cap
    return cap


def device_capture_active() -> bool:
    cap = _CAPTURE
    return cap is not None and cap.active


def device_trace_unit() -> None:
    """Step/tick boundary hook: one global ``None`` check when no capture
    is armed — the loops call this unconditionally every unit."""
    cap = _CAPTURE
    if cap is not None and cap.active:
        cap.unit()


def stop_device_trace() -> Optional[str]:
    """Stop the process-global capture (if any); returns the log dir."""
    cap = _CAPTURE
    if cap is None:
        return None
    return cap.stop()


def maybe_capture_from_env() -> Optional[DeviceTraceCapture]:
    """Arm the env-configured capture once per process: with
    ``DS_TPU_DEVICE_TRACE=<dir>`` set, the FIRST engine init starts a
    capture of ``DS_TPU_DEVICE_TRACE_UNITS`` (default 16) loop units into
    ``<dir>``.  Later calls (more engines, warm-restart replacements) are
    no-ops — one windowed capture per process, not one per engine."""
    global _ENV_ARMED
    raw = os.environ.get(DEVICE_TRACE_ENV, "").strip()
    if not raw or _ENV_ARMED:
        return None
    _ENV_ARMED = True
    units_raw = os.environ.get(DEVICE_TRACE_UNITS_ENV, "").strip()
    units = DEFAULT_CAPTURE_UNITS
    if units_raw:
        try:
            units = int(units_raw)
        except ValueError:
            logger.warning("ignoring malformed $%s=%r (want an int)",
                           DEVICE_TRACE_UNITS_ENV, units_raw)
    try:
        return capture_device_trace(raw, n_units=units)
    except Exception as e:   # pragma: no cover - defensive
        logger.warning("env-armed device trace failed (%s); continuing "
                       "without", e)
        return None
