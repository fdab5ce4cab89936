"""Atomic postmortem bundles: one JSON file per rank with everything.

Every crash path converges here — the watchdog's stall dump, the
PreemptionHandler's emergency snapshot, the excepthook/atexit crash
hooks, a tripped numerics check, the periodic flight-recorder spill,
and an explicit ``observability.dump()``. The bundle is self-contained:

  * the flight-recorder event ring (flight.events()),
  * the telemetry dump (every counter/gauge/histogram),
  * the diagnostics span records + per-step phase table,
  * the compile registry (what XLA built, flops/peak-HBM per program),
  * numerics trips + bisect reports,
  * the typed env-var snapshot and process identity (job/rank/world).

Writes go through the ``_checkpoint_io`` engine path — serialized per
bundle path, committed with write-tmp → fsync → ``os.replace`` so a
kill mid-write leaves the previous complete bundle, never a torn one.
``sync=False`` queues the write on an engine IO thread (the periodic
spill never blocks training); crash paths use ``sync=True``.
``tools/blackbox.py`` merges N ranks' bundles into one chrome trace +
stall report.
"""
from __future__ import annotations

import json
import os
import sys
import time

__all__ = ["dump", "build_bundle", "default_path", "install_crash_hooks",
           "crash_hooks_installed"]

BUNDLE_FORMAT = 1

_hooks = {"installed": False, "prev_excepthook": None, "fh_file": None}


def _jsonable(x):
    try:
        return float(x)
    except (TypeError, ValueError):
        return str(x)


def default_path(rank=None):
    """``<MXTPU_FLIGHTREC_DIR>/mxtpu_blackbox.rank<r>.json``."""
    from . import flight

    try:
        from .. import env as _env

        d = _env.get("MXTPU_FLIGHTREC_DIR") \
            if "MXTPU_FLIGHTREC_DIR" in _env.all_vars() else "."
    except Exception:
        d = os.environ.get("MXTPU_FLIGHTREC_DIR", ".")
    d = d or "."
    if rank is None:
        rank = flight.identity()["rank"]
    return os.path.join(d, f"mxtpu_blackbox.rank{rank}.json")


def build_bundle(reason, extra=None):
    """Assemble the bundle dict. Each section is independently guarded:
    a half-dead process must still produce SOME bundle."""
    from . import flight, numerics

    bundle = {
        "format": BUNDLE_FORMAT,
        "reason": str(reason),
        "time": time.time(),
        "pid": os.getpid(),
        "identity": flight.identity(),
        "events": flight.events(),
        "numerics_trips": numerics.trips(),
    }
    try:
        from .. import env as _env

        bundle["env"] = {name: _jsonable(var.read())
                         for name, var in _env.all_vars().items()}
    except Exception as e:
        bundle["env"] = {"error": repr(e)}
    try:
        from .. import telemetry

        bundle["telemetry"] = telemetry.dump()
    except Exception as e:
        bundle["telemetry"] = {"error": repr(e)}
    try:
        from ..diagnostics import spans as _spans

        bundle["spans"] = _spans.records()
        bundle["step_table"] = {
            str(k): v for k, v in _spans.step_table().items()}
        bundle["trace_context"] = _spans.trace_context()
    except Exception as e:
        bundle["spans"] = []
        bundle["step_table"] = {"error": repr(e)}
    try:
        from ..diagnostics import introspect as _introspect

        bundle["compile_registry"] = {
            f"{b}/{v}": entry
            for (b, v), entry in _introspect.compile_registry().items()}
    except Exception as e:
        bundle["compile_registry"] = {"error": repr(e)}
    try:
        from ..diagnostics import watchdog as _watchdog

        bundle["watchdog_dump"] = _watchdog.last_dump()
    except Exception:
        bundle["watchdog_dump"] = None
    try:
        from . import reqtrace

        # request traces + batch links + SLO table ride in the bundle so
        # tools/blackbox.py can interleave per-request spans with rank
        # spans in the merged chrome trace
        bundle["req_traces"] = reqtrace.traces()
        bundle["req_batches"] = reqtrace.batches()
        bundle["slo"] = reqtrace.slo_status()
    except Exception as e:
        bundle["req_traces"] = []
        bundle["req_batches"] = []
        bundle["slo"] = {"error": repr(e)}
    if extra:
        bundle.update(extra)
    return bundle


def _atomic_write(path, payload):
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def dump(reason="manual", path=None, sync=True, extra=None):
    """Serialize the bundle to ``path`` (default: the per-rank blackbox
    file) through the _checkpoint_io atomic-commit path. Returns the
    bundle path. Never raises on the async path; the sync path raises
    only when even the direct-write fallback fails."""
    if path is None:
        path = default_path()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    payload = json.dumps(build_bundle(reason, extra), default=_jsonable)
    try:
        from ..telemetry import instruments as _instr

        _instr.record_postmortem(str(reason).split(":", 1)[0])
    except Exception:
        pass
    try:
        from .. import _checkpoint_io

        _checkpoint_io.async_run(path, lambda: _atomic_write(path, payload))
        if sync:
            _checkpoint_io.wait_for_path(path)
    except Exception:
        # engine gone (atexit/teardown) or the queued write failed:
        # last-ditch direct write, still atomic
        if sync:
            _atomic_write(path, payload)
        else:
            try:
                _atomic_write(path, payload)
            except Exception:
                pass
    return path


# ---------------------------------------------------------------------------
# crash hooks
# ---------------------------------------------------------------------------


def crash_hooks_installed():
    return _hooks["installed"]


def install_crash_hooks():
    """Arm the crash paths (idempotent):

      * ``sys.excepthook`` — an uncaught exception records a ``crash``
        flight event and writes the bundle before the interpreter dies;
      * ``atexit`` — a final bundle on interpreter shutdown (reason
        ``exit``), so even clean exits leave the black box behind;
      * ``faulthandler`` — hard faults (SIGSEGV/SIGABRT) dump native
        tracebacks next to the bundle (Python can't run there, so this
        is a text sidecar, not a JSON bundle).

    Auto-armed at import when ``MXTPU_FLIGHTREC_CRASHDUMP=1``.
    """
    if _hooks["installed"]:
        return False
    _hooks["installed"] = True

    import atexit

    from . import flight

    prev = sys.excepthook
    _hooks["prev_excepthook"] = prev

    def hook(exc_type, exc, tb):
        try:
            flight.record("crash", error=f"{exc_type.__name__}: {exc}")
            dump(reason=f"crash:{exc_type.__name__}", sync=True)
            _hooks["crash_dumped"] = True
        except Exception:
            pass
        prev(exc_type, exc, tb)

    sys.excepthook = hook

    def on_exit():
        if _hooks.get("crash_dumped"):
            return  # don't overwrite the crash bundle with reason "exit"
        try:
            dump(reason="exit", sync=True)
        except Exception:
            pass

    atexit.register(on_exit)

    try:
        import faulthandler

        rank = flight.identity()["rank"]
        side = os.path.join(
            os.path.dirname(default_path()) or ".",
            f"mxtpu_faulthandler.rank{rank}.txt")
        f = open(side, "w")  # noqa: SIM115 — must outlive this frame
        _hooks["fh_file"] = f
        faulthandler.enable(file=f)
    except Exception:
        pass
    return True
