"""What the program says about itself, for the per-layer readers.

Three sources, all written by ``mxnet_tpu`` and none by the benchmark:

* the span ring (``mxnet_tpu.diagnostics.spans.records()``): host time of
  each part of ``TrainStep.__call__`` -- ``ring``;
* the same spans as profiler annotations (``mxtpu:<name>``) on the host
  planes of a traced run's ``.xplane.pb``, on the clock the device
  operations are on -- ``host_spans``, ``idle_under``;
* the scope of each instruction of the compiled whole-step program
  (``op_scopes`` of the compile registry: instruction name -> JAX name
  stack) -- ``scope_seconds``, ``phase_of``, ``block_of``.

Readers run in the driver's process after the run.  On a program that
has none of this (the parent of the PR that added it) every function
here returns None or an empty result and never raises.
"""
import os

import trace_reduce

PREFIX = "mxtpu:"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_cache = {}


# -- the ring ---------------------------------------------------------------

def ring(run):
    """{span name: [seconds, ...]}: the last ``run["steps"]`` records of
    each name, oldest first -- the window's, since every step of set-up
    came before them."""
    from mxnet_tpu.diagnostics import spans

    out = {}
    for r in spans.records():
        out.setdefault(r["name"], []).append(r["dur"])
    n = int(run.get("steps") or 0)
    return {k: v[-n:] for k, v in out.items()} if n else {}


def per_step_sum(spans_s, names):
    """Per-step sums of the spans ``names``, paired from the newest step
    back; None unless every name was recorded."""
    cols = [spans_s.get(n) for n in names]
    if not cols or not all(cols):
        return None
    n = min(len(c) for c in cols)
    return [sum(c[len(c) - n + i] for c in cols) for i in range(n)]


# -- the traced run's host planes -------------------------------------------

def xplane_of(run):
    """Path of the newest trace of this run's cell, or None."""
    try:
        return trace_reduce.newest_xplane(os.path.join(
            ROOT, ".chipbench", "trace", run["workload"]["name"]))
    except (FileNotFoundError, KeyError):
        return None


def host_spans(xplane, prefix=PREFIX):
    """[(name without prefix, start_ns, dur_ns)] of the program's
    annotations on the host planes, by start time."""
    key = ("host", xplane, prefix)
    if key not in _cache:
        from jax.profiler import ProfileData

        out = []
        for plane in ProfileData.from_file(xplane).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        out.append((ev.name[len(prefix):],
                                    int(ev.start_ns), int(ev.duration_ns)))
        _cache[key] = sorted(out, key=lambda e: e[1])
    return _cache[key]


def devices_of(xplane, platform):
    """{plane: [(name, start_ns, dur_ns)]} as ``trace_reduce.load``."""
    key = ("dev", xplane, platform)
    if key not in _cache:
        _cache[key] = trace_reduce.load(xplane, platform)["devices"]
    return _cache[key]


def idle_under(devices, spans, name):
    """Seconds the first device was idle, inside its window, while a host
    span called ``name`` was open."""
    if not devices:
        return None
    _plane, evs = sorted(devices.items())[0]
    busy = trace_reduce.union((s, s + d) for _n, s, d in evs)
    cover = trace_reduce.union((s, s + d) for n, s, d in spans if n == name)
    if not busy or not cover:
        return None
    idle_ns = 0
    for a, b in zip(busy, busy[1:]):
        for s, e in cover:
            idle_ns += max(0, min(b[0], e) - max(a[1], s))
    return idle_ns / 1e9


# -- the compiled program's scopes ------------------------------------------

def op_scopes(block="whole_step"):
    """{instruction name: op_name} of the compiled ``block`` programs, or
    None where the program keeps no such map."""
    from mxnet_tpu.diagnostics import introspect

    out = None
    for (blk, _variant), entry in introspect.compile_registry().items():
        if blk == block and entry.get("op_scopes"):
            out = dict(out or {})
            # trace_reduce.short_name cuts an event's name at 64
            out.update({k[:64]: v for k, v in entry["op_scopes"].items()})
    return out


def phase_of(scope):
    """forward / backward / optimizer / grad_reduce of a name stack that
    ``TrainStep`` scoped, else None."""
    if "transpose(jvp(" in scope:
        return "backward"
    if "jvp(forward)" in scope or "jvp(loss)" in scope:
        return "forward"
    if "/optimizer/" in scope:
        return "optimizer"
    if "/grad_reduce/" in scope:
        return "grad_reduce"
    return None


def block_of(scope):
    """The innermost block scope (``BatchNorm_bn2``): the last component
    before the primitive that is not a transform such as ``jvp(..)``."""
    for part in reversed(scope.split("/")[:-1]):
        if "(" not in part:
            return part
    return None


def scope_seconds(trace, keep):
    """Device seconds of the operations whose scope ``keep`` accepts,
    over the whole trace; None without a map or without operations."""
    scopes = op_scopes()
    if not scopes or not trace.get("op_s"):
        return None
    return sum(s for name, s in trace["op_s"].items()
               if name in scopes and keep(scopes[name]))


def per_traced_step_ms(trace, run, keep):
    """``scope_seconds`` as milliseconds per traced step."""
    total = scope_seconds(trace, keep)
    if total is None or not run.get("traced_steps"):
        return None
    return total * 1e3 / run["traced_steps"]


# -- what compiled, and when ------------------------------------------------

def xla_compiles_of_setup():
    """{"backend_s", "cache_load_s", "built"}: what JAX compiled or loaded
    up to the last training step, from the program's counters less the
    flight recorder's ``xla_compile`` events that came after that step
    (the plain reference compiles in this process too, after the window).
    None where the program has no such counters."""
    from mxnet_tpu.observability import flight
    from mxnet_tpu.telemetry import instruments as ti

    if not hasattr(ti, "xla_programs_total"):
        return None
    seconds = {k[0]: c.value for k, c in ti.xla_compile_seconds_total.series()}
    programs = {k[0]: c.value for k, c in ti.xla_programs_total.series()}
    if "backend" not in seconds:
        return None
    events = flight.events()
    steps = [e["pc"] for e in events if e["kind"] == "step"]
    later = [e for e in events if e["kind"] == "xla_compile"
             and steps and e["pc"] > steps[-1]]
    return {
        "backend_s": seconds["backend"] - sum(e["seconds"] for e in later),
        "cache_load_s": seconds.get("cache_load", 0.0)
        - sum(e["load_seconds"] for e in later),
        "built": programs.get("built", 0.0)
        - sum(e["how"] == "built" for e in later),
    }
