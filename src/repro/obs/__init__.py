"""Telemetry for the tuned-collective runtime: schedule-keyed trace
spans (`trace`), counters (`metrics`), program spans on the profiler's
clock (`profiler.span`), measured-vs-modeled residuals (`residuals`),
Perfetto/summary artifacts (`export`) and standalone per-task schedule
measurement (`replay`).

Import discipline: this package root pulls in ONLY `trace`, `metrics`
and `profiler`, which depend on nothing inside ``repro.core`` — the dispatch
layer (`core.collectives.dispatch`) imports the trace hook, so anything
heavier here would be a cycle. `residuals`, `export` and `replay` load
lazily on first attribute access (or via an explicit submodule import).
"""
from repro.obs.metrics import MetricsRegistry
from repro.obs.profiler import span
from repro.obs.trace import (
    FakeClock,
    Span,
    TraceRecorder,
    active,
    assign_stream_tags,
    installed,
)

__all__ = [
    "MetricsRegistry", "FakeClock", "Span", "TraceRecorder",
    "active", "assign_stream_tags", "installed", "span",
    "residuals", "export", "replay",
]


def __getattr__(name):
    if name in ("residuals", "export", "replay"):
        import importlib
        return importlib.import_module(f"repro.obs.{name}")
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
