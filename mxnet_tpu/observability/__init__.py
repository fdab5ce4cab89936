"""Observability plane: numerics checking, flight recorder, request
tracing, postmortems.

Coordinated pieces (docs/observability.md):

  * :mod:`~mxnet_tpu.observability.numerics` — a graph pass
    (``MXTPU_NUMERICS=off|step|op``) that instruments captured jaxprs
    with fused is-finite checks and, on a trip, bisects the recorded
    program to the first non-finite equation;
  * :mod:`~mxnet_tpu.observability.flight` — the bounded ring of
    structured runtime events every subsystem reports into;
  * :mod:`~mxnet_tpu.observability.reqtrace` — per-request phase traces
    through the serving pipeline (head-sampled via MXTPU_TRACE_SAMPLE)
    plus the per-class SLO burn-rate plane that gates opsd ``/readyz``;
  * :mod:`~mxnet_tpu.observability.postmortem` — serializes everything
    (events + telemetry + spans + request traces + compile registry +
    env snapshot) into one atomic per-rank bundle that
    ``tools/blackbox.py`` merges across ranks.

Quick use::

    import mxnet_tpu as mx
    mx.observability.record_event("phase", name="warmup done")
    path = mx.observability.dump(reason="manual")   # the black box

Set ``MXTPU_FLIGHTREC_CRASHDUMP=1`` to auto-arm the excepthook /
atexit / faulthandler crash hooks at import.
"""
from __future__ import annotations

import os

from . import flight, numerics, opsd, postmortem, reqtrace  # noqa: F401
from .flight import (  # noqa: F401
    events, record, record_loss, set_identity, trace_id,
)
from .numerics import NonFiniteError  # noqa: F401
from .postmortem import dump, install_crash_hooks  # noqa: F401

__all__ = [
    "flight", "numerics", "opsd", "postmortem", "reqtrace",
    "record", "record_event", "record_loss", "events",
    "set_identity", "trace_id",
    "dump", "install_crash_hooks", "reset",
    "NonFiniteError",
]

record_event = record


def reset():
    """Test hygiene: drop flight events, numerics trip bookkeeping and
    request traces / SLO windows."""
    flight.reset()
    numerics.reset()
    reqtrace.reset()


if os.environ.get("MXTPU_FLIGHTREC_CRASHDUMP", "").lower() \
        not in ("", "0", "false", "off"):
    install_crash_hooks()

# MXTPU_OPS_PORT=<port> starts the live ops server at import (the
# per-process HTTP plane supervisors poll); unset/0 touches nothing.
opsd.start_from_env()
