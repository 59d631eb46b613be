"""What this process's ``jax.jit`` spent building programs, counted by JAX
itself.

JAX reports every trace, lowering and backend compile (or fetch from the
persistent compilation cache) through ``jax.monitoring``. ``install()``
registers listeners that sum those events, once a process however often it
is called; ``snapshot()`` returns the sums. A callback runs only when
something is traced or compiled, so a program that has warmed its shapes
pays nothing, and a jump of ``jit_programs`` between two snapshots says
that something compiled in between.

The sums are the PROCESS's: every thread's programs, whoever asked for
them. ``mine()`` gives the calling thread's own share, for a caller that
wants the part of a stretch of its own work that went into the jit (the
serving engine's set-up, ``serve/llm.py``).

Not ``util/metrics.py``: its flush is a synchronous GCS ``kv_put``.
"""

from __future__ import annotations

import threading
from typing import Dict, Tuple

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_NESTING = (_TRACE, _LOWER)  # stretches that can hold one another
_DURATIONS = {
    _TRACE: "jit_trace_lower_s", _LOWER: "jit_trace_lower_s",
    _BACKEND: "jit_backend_s", _RETRIEVAL: "jit_cache_retrieval_s",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jit_cache_hits",
    "/jax/compilation_cache/cache_misses": "jit_cache_misses",
}

_lock = threading.Lock()
_installed = False
_sums: Dict[str, float] = {
    "jit_trace_lower_s": 0.0, "jit_backend_s": 0.0, "jit_programs": 0,
    "jit_cache_hits": 0, "jit_cache_misses": 0,
    "jit_cache_retrieval_s": 0.0,
}


class _Mine(threading.local):
    """One thread's own sums, and how deep it stands in nested traces
    and lowerings."""

    def __init__(self):
        self.depth = 0
        self.trace_lower_s = 0.0
        self.backend_s = 0.0


_mine = _Mine()


def _on_scalar(event: str, _value, **_kw) -> None:
    # JAX records a stretch's start as a scalar. A function jitted inside
    # a jitted function is traced inside its caller's trace (or inside a
    # lowering rule), and only the outermost duration is time that passed
    # once
    if event in _NESTING:
        _mine.depth += 1


def _on_duration(event: str, seconds: float, **_kw) -> None:
    key = _DURATIONS.get(event)
    if key is None:
        return
    if event in _NESTING:
        _mine.depth = max(0, _mine.depth - 1)
        if _mine.depth:
            return
        _mine.trace_lower_s += seconds
    elif event == _BACKEND:
        _mine.backend_s += seconds
    with _lock:
        _sums[key] += seconds
        if event == _BACKEND:
            _sums["jit_programs"] += 1


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        with _lock:
            _sums[key] += 1


def install() -> None:
    """Start counting; the second and later calls do nothing."""
    global _installed
    import jax.monitoring as monitoring

    with _lock:
        if _installed:
            return
        _installed = True
    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def snapshot() -> Dict[str, float]:
    """The process's sums since ``install()``:

    - ``jit_trace_lower_s`` seconds tracing Python into jaxprs and
      lowering jaxprs to StableHLO (of nested ones the outermost alone):
      paid BEFORE the compilation cache is asked, on every start;
    - ``jit_backend_s`` seconds in the backend's compile, or in fetching
      the executable from the persistent cache in its place, and
      ``jit_programs``, how many programs that was;
    - ``jit_cache_hits`` programs fetched from the persistent cache,
      ``jit_cache_retrieval_s`` the seconds that took (part of
      ``jit_backend_s``), ``jit_cache_misses`` programs compiled and
      written to it (a program under the cache's size or compile-time
      threshold is neither). For whoever starts a process and wants to
      know whether the cache served it: hits against misses says whether
      the start was warm (a directory that was not mounted, or a key
      that moved with a flag or a version, reads 0 hits), and
      ``jit_cache_retrieval_s`` against ``jit_backend_s`` says whether
      what a warm start still pays the backend is the read and the
      deserialization of executables (a slow disk, large programs) or
      compiles of programs the cache never keeps.
    """
    with _lock:
        return dict(_sums)


def mine() -> Tuple[float, float]:
    """(trace + lower seconds, backend seconds) of the programs the
    CALLING thread built: subtract two readings around a stretch of the
    thread's own work."""
    return _mine.trace_lower_s, _mine.backend_s
