"""Process-wide XLA compile counter over ``jax.monitoring``.

The ``/jax/core/compile/backend_compile_duration`` duration event fires once
per program the in-memory jit caches miss (jit cache hits don't), which makes
it the honest instrument for zero-recompile contracts (serving admission,
bench steady state).  With the persistent cache on
(``utils/compile_cache.py``) the event still fires when the executable is
read back from disk; :func:`persistent_cache_counter` tells those apart.
``jax.monitoring`` has no unregister, so each listener is a process-wide
singleton — every caller shares one event list and takes deltas around the
section it cares about.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_EVENTS: List[float] = []    # one duration (seconds) per backend compile
_INSTALLED = False


def compile_counter() -> Callable[[], int]:
    """Install (once) the backend-compile listener and return a zero-arg
    ``count()``; callers snapshot it before/after a section and diff."""
    global _INSTALLED
    if not _INSTALLED:
        _INSTALLED = True
        import jax.monitoring

        def _listen(name, duration, **kw):
            if name == _BACKEND_COMPILE_EVENT:
                _EVENTS.append(float(duration))

        jax.monitoring.register_event_duration_secs_listener(_listen)
    return lambda: len(_EVENTS)


def compile_seconds() -> Callable[[], float]:
    """Zero-arg ``seconds()``: time spent in the compiles
    :func:`compile_counter` counts (set-up time, reported apart from any
    steady-state figure)."""
    compile_counter()
    return lambda: sum(_EVENTS)


_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_EVENTS: Dict[str, int] = {_CACHE_HIT_EVENT: 0, _CACHE_MISS_EVENT: 0}
_CACHE_INSTALLED = False


def persistent_cache_counter() -> Callable[[], Tuple[int, int]]:
    """Install (once) a listener on the persistent compilation cache and
    return a zero-arg ``counts() -> (hits, misses)``: executables read back
    from the cache directory, and programs compiled because it lacked them.
    Both stay 0 while the persistent cache is off."""
    global _CACHE_INSTALLED
    if not _CACHE_INSTALLED:
        _CACHE_INSTALLED = True
        import jax.monitoring

        def _listen(name, **kw):
            if name in _CACHE_EVENTS:
                _CACHE_EVENTS[name] += 1

        jax.monitoring.register_event_listener(_listen)
    return lambda: (_CACHE_EVENTS[_CACHE_HIT_EVENT],
                    _CACHE_EVENTS[_CACHE_MISS_EVENT])
