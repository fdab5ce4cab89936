"""Diagnostics: span tracing, XLA compile introspection, hang watchdog.

The observability layer on top of telemetry/ (counters): answers *where*
a step's time went (spans + per-step phase table), *what* XLA compiled
(flops / bytes / peak-HBM per block variant), and *why* a job is hung
(watchdog stack/span dumps). See docs/diagnostics.md.

    from mxnet_tpu import diagnostics

    with diagnostics.span("fwd", cat="fwd"):
        ...
    print(diagnostics.report())

Env knobs: MXTPU_DIAGNOSTICS, MXTPU_DIAG_RING_CAPACITY,
MXTPU_DIAG_COMPILE, MXTPU_WATCHDOG, MXTPU_WATCHDOG_TIMEOUT_S,
MXTPU_WATCHDOG_FILE, MXTPU_WATCHDOG_RAISE.
"""
from __future__ import annotations

from . import introspect, spans, startup, watchdog
from .introspect import (
    capture_compile,
    compile_registry,
    device_memory,
    format_compile_table,
    update_device_memory_gauge,
)
from .report import report
from .startup import format_startup_table, startup_report
from .spans import (
    all_stacks,
    current_stack,
    current_step,
    format_step_table,
    mark_step,
    records,
    span,
    step_table,
)
from .watchdog import guard

__all__ = [
    "span", "records", "step_table", "format_step_table",
    "mark_step", "current_step", "current_stack", "all_stacks",
    "capture_compile", "compile_registry", "format_compile_table",
    "device_memory", "update_device_memory_gauge",
    "guard", "report", "reset",
    "startup_report", "format_startup_table",
    "spans", "introspect", "startup", "watchdog",
]


def reset():
    """Clear spans, the compile registry, the kept start-up reductions
    and watchdog state (tests)."""
    spans.reset()
    introspect.reset()
    startup.reset()
    watchdog.reset()
