"""Process-wide counts of jit-cache misses and XLA compiles (DESIGN.md §6).

JAX reports both through ``jax.monitoring``:

  * ``/jax/core/compile/jaxpr_trace_duration`` — a function was traced
    to a jaxpr: a jit-cache miss. Tracing one jitted function traces
    every jitted function it calls that misses too (a new batch shape
    of ``device_anns`` fires the event some 900 times), and lowering it
    can trace more (the Pallas interpreter's kernels, on the CPU). So
    only a trace outside any other step of the compile pipeline counts
    (one per jit-cache miss of what the caller dispatched): each of
    the three events below opens a level with its enter-time scalar
    and closes it with its duration, per thread;
  * ``/jax/core/compile/jaxpr_to_mlir_module_duration`` — lowering;
    counted nowhere, it only opens a level;
  * ``/jax/core/compile/backend_compile_duration`` — one XLA compile
    (or load from the persistent compilation cache) of a lowered
    program.

The listeners are registered once per process, on the first call of
``compile_counts`` — JAX's listener lists are themselves process-wide
— so a program that never asks makes no monitoring calls at all.
"""
from __future__ import annotations

import threading
from typing import Optional, Tuple

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LEVELS = (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT)


class _Counts:
    def __init__(self):
        self.traces = 0
        self.compiles = 0
        self._depth = threading.local()
        self._lock = threading.Lock()

    def on_scalar(self, event: str, value, **kwargs) -> None:
        if event in _LEVELS:
            self._depth.n = getattr(self._depth, "n", 0) + 1

    def on_duration(self, event: str, secs: float, **kwargs) -> None:
        if event not in _LEVELS:
            return
        depth = max(getattr(self._depth, "n", 0) - 1, 0)
        self._depth.n = depth
        with self._lock:
            if event == TRACE_EVENT and depth == 0:
                self.traces += 1
            elif event == COMPILE_EVENT:
                self.compiles += 1


_COUNTS: Optional[_Counts] = None
_REGISTER = threading.Lock()


def compile_counts() -> Tuple[int, int]:
    """(outermost traces, XLA compiles) seen since the first call in
    this process; the first call registers the listeners and reads
    (0, 0). Callers take differences around the work they attribute."""
    global _COUNTS
    with _REGISTER:
        if _COUNTS is None:
            import jax
            counts = _Counts()
            jax.monitoring.register_scalar_listener(counts.on_scalar)
            jax.monitoring.register_event_duration_secs_listener(
                counts.on_duration)
            _COUNTS = counts
    return _COUNTS.traces, _COUNTS.compiles
