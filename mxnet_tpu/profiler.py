"""Profiler: XLA/XPlane device traces + host-side chrome-trace events.

Reference: src/profiler/ (typed stats in per-device buffers dumped as Chrome
chrome://tracing JSON + aggregate summaries, python/mxnet/profiler.py).

TPU re-design: two complementary layers —
  * device time: jax.profiler traces (XPlane) capture XLA compute, HBM
    transfers, and collectives for TensorBoard/Perfetto, replacing the
    engine-op timeline (set_state('run'/'stop'));
  * host time: Task/Event/Frame/Counter and `scope()` record host-side
    spans into an in-memory buffer that dump() writes as the same Chrome
    trace-event JSON the reference emitted (profiler.dump → profile.json,
    viewable at chrome://tracing), and dumps() aggregates like
    aggregate_stats (count/total/min/max per name).
`scope()` additionally enters jax.named_scope, so the same name shows up
attached to HLO ops inside the device trace.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import jax

_config = {"filename": "profile.json", "profile_all": False,
           "aggregate_stats": True}
_running = False
_paused = False
_trace_dir = None

_events = []  # chrome trace events: dicts with name/ph/ts/dur/pid/tid
_events_lock = threading.Lock()
_t_origin = time.perf_counter()


def _now_us():
    return (time.perf_counter() - _t_origin) * 1e6


def _host_recording():
    """Host events record only while the profiler runs (reference: nothing
    is recorded before set_state('run')) or with profile_all set."""
    return (_running or _config.get("profile_all")) and not _paused


def _record(name, t0_us, dur_us, cat="host"):
    if not _host_recording():
        return
    with _events_lock:
        _events.append({
            "name": name, "cat": cat, "ph": "X", "ts": t0_us,
            "dur": dur_us, "pid": os.getpid(),
            "tid": threading.get_ident() % 100000,
        })


def record_counter_event(name, value, cat="telemetry"):
    """Append a chrome counter event (`"ph": "C"`) to the host buffer —
    the telemetry bridge's entry point (telemetry/chrome.py), gated like
    every host event. Returns 1 if recorded, 0 if not recording."""
    if not _host_recording():
        return 0
    with _events_lock:
        _events.append({"name": name, "cat": cat, "ph": "C",
                        "ts": _now_us(), "pid": os.getpid(),
                        "args": {"value": float(value)}})
    return 1


def set_config(**kwargs):
    """Accepts reference kwargs (filename, profile_all, aggregate_stats...)."""
    _config.update(kwargs)


def set_state(state="stop", profile_process="worker"):  # noqa: ARG001
    global _running, _trace_dir
    if state == "run" and not _running:
        _trace_dir = _config.get("trace_dir") or os.path.join(
            os.path.dirname(os.path.abspath(_config["filename"])) or ".",
            "jax_trace",
        )
        jax.profiler.start_trace(_trace_dir)
        _running = True
    elif state == "stop" and _running:
        jax.profiler.stop_trace()
        _running = False


def start():
    set_state("run")


def stop():
    set_state("stop")


def dump(finished=True, profile_process="worker"):  # noqa: ARG001
    """Write host-side events as Chrome trace JSON to `filename`
    (reference: MXDumpProfile → chrome://tracing file); stops any live
    device trace first."""
    if _running:
        stop()
    with _events_lock:
        events = list(_events)
        _events.clear()  # dumped events are consumed (bounded memory)
    with open(_config["filename"], "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return _config["filename"]


def dumps(reset=False):
    """Aggregate summary table (reference: aggregate_stats dumps)."""
    with _events_lock:
        events = list(_events)
        if reset:
            _events.clear()
    agg = {}
    for e in events:
        if e.get("ph") != "X":  # counters carry no duration
            continue
        a = agg.setdefault(e["name"], [0, 0.0, float("inf"), 0.0])
        a[0] += 1
        a[1] += e["dur"]
        a[2] = min(a[2], e["dur"])
        a[3] = max(a[3], e["dur"])
    lines = [f"{'Name':<32}{'Count':>8}{'Total(ms)':>12}{'Min(ms)':>10}"
             f"{'Max(ms)':>10}"]
    for name, (cnt, tot, mn, mx) in sorted(agg.items()):
        lines.append(f"{name:<32}{cnt:>8}{tot / 1e3:>12.3f}"
                     f"{mn / 1e3:>10.3f}{mx / 1e3:>10.3f}")
    if _trace_dir:
        lines.append(f"device trace dir: {_trace_dir}")
    return "\n".join(lines)


@contextlib.contextmanager
def scope(name="<unk>"):
    """Name scope: annotates HLO (device trace) and records a host span
    (reference: profiler.Scope / ProfilerScope, profiler.h:1339)."""
    t0 = _now_us()
    try:
        with jax.named_scope(name):
            yield
    finally:
        # record even when the body raises — the failing region is exactly
        # the one worth seeing in the trace
        _record(f"scope::{name}", t0, _now_us() - t0)


class Task:
    """Named task timing (reference: profiler.Task) — host wall timing,
    recorded into the chrome trace on each stop."""

    _kind = "task"

    def __init__(self, name, domain=None):  # noqa: ARG002
        self.name = name
        self._t0 = None
        self.elapsed = 0.0

    def start(self):
        self._t0 = time.perf_counter()
        self._ts_us = _now_us()

    def stop(self):
        if self._t0 is not None:
            dur = time.perf_counter() - self._t0
            self.elapsed += dur
            _record(f"{self._kind}::{self.name}", self._ts_us, dur * 1e6)
            self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Frame(Task):
    _kind = "frame"


class Event(Task):
    _kind = "event"


class Counter:
    """Named counter (reference: profiler.Counter); value changes are
    recorded as chrome counter events."""

    def __init__(self, name, domain=None, value=0):  # noqa: ARG002
        self.name = name
        self.value = value

    def _emit(self):
        if _host_recording():
            with _events_lock:
                _events.append({"name": f"counter::{self.name}", "ph": "C",
                                "ts": _now_us(), "pid": os.getpid(),
                                "args": {"value": self.value}})

    def set_value(self, v):
        self.value = v
        self._emit()

    def increment(self, delta=1):
        self.value += delta
        self._emit()

    def decrement(self, delta=1):
        self.value -= delta
        self._emit()

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


def pause(profile_process="worker"):  # noqa: ARG001
    global _paused
    _paused = True


def resume(profile_process="worker"):  # noqa: ARG001
    global _paused
    _paused = False


class Marker:
    """Instant marker (reference: profiler.Marker — mark() drops an
    instant event into the trace)."""

    def __init__(self, name, domain=None):  # noqa: ARG002
        self.name = name

    def mark(self, scope="process"):
        if _host_recording():
            with _events_lock:
                _events.append({"name": f"marker::{self.name}", "ph": "i",
                                "ts": _now_us(), "pid": os.getpid(),
                                "s": {"process": "p", "thread": "t",
                                      "global": "g"}.get(scope, "p")})


class Domain:
    """Named grouping for profiler objects (reference: profiler.Domain —
    a factory whose name prefixes everything created under it)."""

    def __init__(self, name):
        self.name = name

    def __str__(self):
        return self.name

    def new_counter(self, name, value=0):
        return Counter(f"{self.name}::{name}", self, value)

    def new_task(self, name):
        return Task(f"{self.name}::{name}", self)

    def new_frame(self, name):
        return Frame(f"{self.name}::{name}", self)

    def new_event(self, name):
        return Event(f"{self.name}::{name}", self)

    def new_marker(self, name):
        return Marker(f"{self.name}::{name}", self)

