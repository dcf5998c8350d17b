"""Persistent XLA compilation cache + compile-time observability.

``enable_compilation_cache`` turns on JAX's on-disk compilation cache so
repeat invocations of the drivers/benchmarks skip XLA compilation entirely
(the scan-fused round engine compiles one executable per chunk shape; with
the cache warm even the first chunk of a fresh process is a disk hit).
The cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set and in
``<checkout>/.jax_cache`` otherwise -- one fixed path, since the path is
part of what makes a later process find the entries again.

``CompileWatcher`` taps ``jax.monitoring`` to count backend compiles and
accumulate the time spent in them -- this is how the round-engine benchmark
splits ``first_round_ms`` into compile vs execute, and how CI asserts the
no-mid-run-recompile contract from *measured* events rather than by
inspection.
"""

from __future__ import annotations

import os
import pathlib
import time
from typing import List, Optional, Tuple

import jax

__all__ = ["enable_compilation_cache", "compilation_cache_dir",
           "CompileWatcher"]

#: cache directory when $JAX_COMPILATION_CACHE_DIR is unset (gitignored)
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

#: monitoring event emitted once per XLA backend compile -- the recompile
#: *count* tracks only these (one per executable built)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the full compilation pipeline for the compile/execute *time* split:
#: tracing + lowering + backend compile all stall the dispatching host
_PIPELINE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def compilation_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``REPO_CACHE_DIR``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_CACHE_DIR))


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`compilation_cache_dir`.

    The min-compile-time threshold is dropped to 0 so even the small chunk
    executables of the scan engine are cached.  Idempotent; returns the
    directory in use.
    """
    cache_dir = compilation_cache_dir()
    pathlib.Path(cache_dir).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


class CompileWatcher:
    """Counts backend compiles and sums their duration via jax.monitoring.

    Listeners cannot be unregistered on this jax version, so one watcher
    is installed per process and windows are taken with :meth:`snapshot` /
    ``since``.  Durations come from the monitoring events; timestamps are
    recorded at event receipt so a window can be attributed to a wall-clock
    span (e.g. "compiles during the first round").
    """

    _installed: Optional["CompileWatcher"] = None

    def __init__(self):
        # (t_received, secs, is_backend_compile)
        self.events: List[Tuple[float, float, bool]] = []

        def _listen(event: str, secs: float, **kw):
            if event in _PIPELINE_EVENTS:
                self.events.append((time.perf_counter(), float(secs),
                                    event == _COMPILE_EVENT))

        jax.monitoring.register_event_duration_secs_listener(_listen)

    @classmethod
    def install(cls) -> "CompileWatcher":
        if cls._installed is None:
            cls._installed = cls()
        return cls._installed

    def snapshot(self) -> int:
        """Marker for a window start: the current event count."""
        return len(self.events)

    def since(self, mark: int, t_start: float | None = None,
              t_end: float | None = None) -> Tuple[int, float]:
        """(backend_compile_count, total_pipeline_secs) after ``mark``,
        optionally restricted to events received in [t_start, t_end]
        perf-counter time.  The count tracks executables built; the
        seconds include tracing + lowering + backend compile (the whole
        host stall a cold dispatch pays)."""
        window = self.events[mark:]
        if t_start is not None:
            window = [e for e in window if e[0] >= t_start]
        if t_end is not None:
            window = [e for e in window if e[0] <= t_end]
        return (sum(1 for e in window if e[2]),
                sum(e[1] for e in window))
