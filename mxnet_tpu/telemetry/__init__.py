"""mxnet_tpu.telemetry — runtime counters/gauges/histograms + exporters.

The runtime's observability layer (ISSUE 1): a process-wide, thread-safe
metrics registry instrumented through the hot layers —

  * gluon/block.py       jit compile count + wall time, hybridize fallbacks
  * ndarray / engine.py  host<->device transfer count+bytes, sync points
  * kvstore / parallel   collective call count, bytes, dispatch time
  * gluon/trainer.py     step count, step-time histogram, examples/sec, MFU

— with three sinks:

  * ``telemetry.dump()``            JSON snapshot
  * ``telemetry.prometheus_text()`` Prometheus text exposition format
  * ``telemetry.emit_chrome_counters()``  chrome-trace counter events into
    the profiler.py buffer (metrics on the profiler timeline)

Quick use::

    from mxnet_tpu import telemetry
    ... train ...
    print(telemetry.prometheus_text())
    snap = telemetry.dump()
    snap["jit_compile_total"]["samples"]  # per-block compile counts

``MXTPU_TELEMETRY=0`` disables collection at import (every record helper
early-outs on one bool check); ``telemetry.disable()``/``enable()`` toggle
at runtime, ``telemetry.reset()`` zeroes every series.

Full metric catalog: docs/telemetry.md.
"""
from __future__ import annotations

from .registry import (  # noqa: F401
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    REGISTRY,
    counter,
    gauge,
    histogram,
    enable,
    disable,
    enabled,
    reset,
)
from .exporters import dump, prometheus_text, write_prometheus  # noqa: F401
from .chrome import emit_chrome_counters  # noqa: F401
from . import promparse  # noqa: F401
from . import instruments  # noqa: F401
from .instruments import (  # noqa: F401
    nbytes_of,
    observe_step,
    record_collective,
    record_compile,
    record_fallback,
    record_serve_batch,
    record_serve_request,
    record_sync,
    record_trace,
    record_transfer,
    set_flop_budget,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "DEFAULT_BUCKETS",
    "counter", "gauge", "histogram",
    "enable", "disable", "enabled", "reset",
    "dump", "prometheus_text", "write_prometheus", "emit_chrome_counters",
    "instruments", "promparse",
    "nbytes_of", "observe_step", "record_collective", "record_compile",
    "record_fallback", "record_serve_batch", "record_serve_request",
    "record_sync", "record_trace", "record_transfer", "set_flop_budget",
]
