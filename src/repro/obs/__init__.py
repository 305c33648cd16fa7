"""repro.obs — the observability plane (DESIGN.md §6).

Layers over the search/serving stack:

  * round-granular device tracing (``roundlog`` +
    ``DeviceSearchParams.trace_rounds``) — exact per-round records of
    the batched while-loop, a lossless refinement of ``IOStats``;
  * host span/event tracing (``trace``, injectable ``clock``), whose
    spans also land in the JAX profiler's trace, the process's
    jit-cache-miss and compile counter (``compiles``), and the serving
    ``metrics`` registry the coordinator/stores report through;
  * ``calibrate`` (measured-vs-modeled ``CostModel`` fitting into
    stored presets).

Device time per search stage comes from the profiler trace itself: the
search's ops carry ``core.device_search.STAGES`` names.
"""
from repro.obs.calibrate import (CalibrationPreset, CalibrationSample,
                                 calibrate, fit_cost_model,
                                 load_calibrated)
from repro.obs.clock import ManualClock, WallClock
from repro.obs.compiles import compile_counts
from repro.obs.metrics import (Counter, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.roundlog import (N_ROUND_COLS, ROUND_LOG_COLS,
                                RoundRecord, fold_round_log,
                                round_log_totals)
from repro.obs.trace import TraceEvent, Tracer, manual_tracer

__all__ = [
    "CalibrationPreset", "CalibrationSample", "calibrate",
    "fit_cost_model", "load_calibrated", "ManualClock", "WallClock",
    "compile_counts", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "N_ROUND_COLS", "ROUND_LOG_COLS", "RoundRecord",
    "fold_round_log", "round_log_totals", "TraceEvent", "Tracer",
    "manual_tracer",
]
