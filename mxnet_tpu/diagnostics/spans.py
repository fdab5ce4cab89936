"""Span-based step tracer: nested, thread-safe host spans in a ring buffer.

The telemetry registry (ISSUE 1) answers "how many / how much"; spans
answer "WHERE did this step's milliseconds go", and where the seconds
before the first step went. Every instrumented layer wraps its region in
``diagnostics.span(name, cat=phase)``:

  * gluon/train_step.py  ``train_step`` around one TrainStep call and its
                         parts: ``.prologue``, ``.operands``, ``whole_step``
                         (the compiled call, ``fwd``), ``.compile_capture``
                         (only on a step that compiled), ``.writeback``,
                         ``.bookkeeping``; ``train_step.build`` once
  * gluon/trainer.py     step / collective(allreduce) / optimizer phases;
                         ``trainer.create_states`` when state is made
  * gluon/block.py       the CachedOp call path (``fwd`` phase, compile);
                         ``block.initialize``
  * amp                  ``amp.convert`` (the offline cast of the weights)
  * introspect.py        ``compile_capture.lower`` / ``.compile`` /
                         ``.text`` / ``.op_scopes`` under a capture
  * autograd.backward    the ``bwd`` phase
  * engine.py            waitall / wait_to_read (``sync`` phase)
  * kvstore + parallel   collective dispatch (``collective`` phase)
  * gluon/data loader    batch fetch (``data`` phase)
  * checkpoint, serving  ``checkpoint`` and ``serve`` phases

and two sites write finished records with :func:`record`, because what
they time has ended when they hear of it: ``mxnet_tpu/__init__.py``
(``startup.import``) and the one jax.monitoring listener of
telemetry/instruments.py (``xla.trace`` / ``xla.lower`` / ``xla.backend``
/ ``xla.cache_load``, one per program and stage, with the program's name
as ``fun``); device.py spans the process's first device resolution
(``startup.backend``).  diagnostics/startup.py reduces those to the
start-up report (docs/diagnostics.md, "The spans of start-up").

Records land in a bounded ring (``MXTPU_DIAG_RING_CAPACITY``, default
4096 — old spans fall off, memory stays bounded on infinite loops), each
tagged with the training-step index live at the time and the name of the
span that encloses it (``parent``), so :func:`step_table` can pivot the
ring into a per-step phase breakdown.

A span also enters ``jax.profiler.TraceAnnotation("mxtpu:" + name)``
(``StepTraceAnnotation`` when it carries a ``step_num``): while a
``jax.profiler`` trace is being taken the span lands in the
``.xplane.pb`` host plane, ON THE CLOCK THE DEVICE OPERATIONS ARE ON, so
XProf / Perfetto show ``mxtpu:train_step`` above the fusions it
enqueued; outside a trace the annotation costs one "is a session
active" check.  This module is the only user of either annotation class.

``MXTPU_DIAGNOSTICS=0`` disables collection at import; every helper
early-outs on one bool check, so instrumented hot paths cost one branch
when off.
"""
from __future__ import annotations

import collections
import os
import threading
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = [
    "span", "record", "enabled", "enable", "disable", "reset",
    "records", "set_ring_capacity", "ring_capacity",
    "current_stack", "all_stacks",
    "mark_step", "current_step",
    "set_trace_context", "trace_context",
    "step_table", "format_step_table",
    "PHASES", "ANNOTATION_PREFIX", "STEP_CAT",
]

# every span's profiler annotation is named ANNOTATION_PREFIX + span name
ANNOTATION_PREFIX = "mxtpu:"
# category of the span around one whole training step (TrainStep): its
# children carry the phases, so step_table leaves it out of the sums
STEP_CAT = "step"

# the phase vocabulary step_table pivots on (free-form cats still record;
# they land in the 'other' column). "serve" is the serving engine's
# batch-execution phase (serving/engine.py; docs/serving.md);
# "checkpoint" covers snapshot capture/restore and preemption saves
# (checkpoint/manager.py; docs/checkpointing.md).
PHASES = ("data", "fwd", "bwd", "collective", "optimizer", "sync",
          "compile", "checkpoint", "serve")

def _env_get(name, default):
    # typed env registry when importable (this module loads very early;
    # a partially-initialized package must not break span recording)
    try:
        from .. import env as _env

        if name in _env.all_vars():
            return _env.get(name)
    except Exception:
        pass
    raw = os.environ.get(name)
    if raw is None:
        return default
    if isinstance(default, bool):
        return raw.lower() not in ("", "0", "false", "off")
    try:
        return type(default)(raw)
    except (TypeError, ValueError):
        return default


_enabled = bool(_env_get("MXTPU_DIAGNOSTICS", True))

_DEFAULT_CAPACITY = int(_env_get("MXTPU_DIAG_RING_CAPACITY", 4096))
_ring = collections.deque(maxlen=max(1, _DEFAULT_CAPACITY))
_ring_lock = threading.Lock()

_tls = threading.local()

# tid -> the thread's live span stack (shared view for the watchdog dump;
# entries are (name, cat, t0). The list object is the SAME one _tls holds,
# so reads here see pushes/pops without cross-thread bookkeeping.)
_open_stacks = {}
_open_lock = threading.Lock()


def _reinit_after_fork():
    # spans record from mxtpu service threads; a fork landing inside a
    # ring/stack critical section (dataloader workers fork from a
    # threaded parent) would leave the lock held forever in the child
    global _ring_lock, _open_lock
    _ring_lock = threading.Lock()
    _open_lock = threading.Lock()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_reinit_after_fork)

_step = [0]  # training-step index, bumped by Trainer.step via mark_step()

# cross-rank trace correlation (observability.flight.set_identity pushes
# the process's job/rank here; with the step index already on every
# record, (job, step) is the trace ID tools/blackbox.py aligns ranks on)
_trace_ctx = {}


def set_trace_context(job=None, rank=None):
    """Stamp (job, rank) onto every subsequently recorded span."""
    if job is not None:
        _trace_ctx["job"] = str(job)
    if rank is not None:
        _trace_ctx["rank"] = int(rank)


def trace_context():
    return dict(_trace_ctx)


def enabled():
    return _enabled


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def set_ring_capacity(n):
    """Rebound the ring (existing records are kept up to the new cap);
    returns the previous capacity."""
    global _ring
    n = max(1, int(n))
    with _ring_lock:
        prev = _ring.maxlen
        _ring = collections.deque(_ring, maxlen=n)
    return prev


def ring_capacity():
    return _ring.maxlen


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
        with _open_lock:
            # prune stacks of dead threads while we hold the lock anyway
            live = {t.ident for t in threading.enumerate()}
            for tid in [t for t in _open_stacks if t not in live]:
                del _open_stacks[tid]
            _open_stacks[threading.get_ident()] = st
    return st


class span:
    """Record a nested host span: ``with span(name, cat): ...``.

    Thread-safe; one branch when disabled.  The ring record keeps wall
    times from ``time.perf_counter()``, the nesting depth, the enclosing
    span's name (``parent``), the current step index and ``kv`` (a fixed
    field wins over a ``kv`` of its name); the profiler annotation
    (module docstring) carries ``kv`` as event stats.
    ``step_num`` marks the outermost span of a training step: the
    profiler then groups the device's work under that step."""

    __slots__ = ("name", "cat", "step_num", "kv", "_t0", "_ann", "_st",
                 "_step")

    def __init__(self, name, cat="host", step_num=None, **kv):
        self.name, self.cat = name, cat
        self.step_num, self.kv = step_num, kv
        self._st = None

    def __enter__(self):
        if not _enabled:
            return self
        st = self._st = _stack()
        if self.step_num is None:
            ann = TraceAnnotation(ANNOTATION_PREFIX + self.name, **self.kv)
        else:
            ann = StepTraceAnnotation(ANNOTATION_PREFIX + self.name,
                                      step_num=self.step_num, **self.kv)
        ann.__enter__()
        self._ann = ann
        # a span belongs to the step it began in (TrainStep's own
        # bookkeeping span advances the index before it closes)
        self._step = _step[0]
        self._t0 = time.perf_counter()
        st.append((self.name, self.cat, self._t0))
        return self

    def __exit__(self, exc_type, exc, tb):
        # record even when the body raises — the failing region is
        # exactly the one worth seeing (profiler.scope does the same)
        st = self._st
        if st is None:
            return False
        t1 = time.perf_counter()
        self._st = None
        st.pop()
        self._ann.__exit__(exc_type, exc, tb)
        rec = {
            "name": self.name, "cat": self.cat,
            "t0": self._t0, "dur": t1 - self._t0,
            "tid": threading.get_ident(),
            "depth": len(st),
            "parent": st[-1][0] if st else None,
            "step": self._step,
        }
        if self.kv:
            rec = {**self.kv, **rec}
        if _trace_ctx:
            rec.update(_trace_ctx)
        with _ring_lock:
            _ring.append(rec)
        return False


def record(name, cat, t0, dur, **kv):
    """Put a FINISHED record on the ring: ``dur`` seconds that began at
    ``t0`` (``time.perf_counter()``'s clock), for a caller that hears of
    the work only when it has ended (a jax.monitoring duration event, the
    package's own import).  It carries ``kv``, ``backdated: True``, and as
    ``depth`` / ``parent`` / ``step`` what is open on the calling thread
    NOW, i.e. at the end of what it timed.  No profiler annotation: one
    cannot be entered late, so a back-dated record is on the ring only
    and never in a device trace."""
    if not _enabled:
        return
    st = getattr(_tls, "stack", None)
    rec = {
        **kv,
        "name": name, "cat": cat, "t0": t0, "dur": dur,
        "tid": threading.get_ident(),
        "depth": len(st) if st else 0,
        "parent": st[-1][0] if st else None,
        "step": _step[0],
        "backdated": True,
    }
    if _trace_ctx:
        rec.update(_trace_ctx)
    with _ring_lock:
        _ring.append(rec)


def records():
    """Snapshot of the ring, oldest first BY TIME OF WRITING -- which for
    every record is the end of what it timed: a span is written when it
    closes, a back-dated record (:func:`record`) when its work ended.  So
    ``t0 + dur`` never decreases along one thread's records, a child comes
    before the span that encloses it, and ``t0`` alone is in no order."""
    with _ring_lock:
        return list(_ring)


def reset():
    """Drop recorded spans and rewind the step counter (open spans on
    other threads keep running and will record on exit)."""
    with _ring_lock:
        _ring.clear()
    _step[0] = 0


def mark_step():
    """Advance the training-step index (Trainer.step calls this on
    completion; spans recorded before the Nth call belong to step N)."""
    _step[0] += 1
    return _step[0]


def current_step():
    return _step[0]


def current_stack():
    """Names of the calling thread's open spans, outermost first."""
    return [name for name, _cat, _t0 in getattr(_tls, "stack", ())]


def all_stacks():
    """{thread_ident: [open span names]} across ALL threads — the
    watchdog's view of what everyone was inside when a hang fired."""
    with _open_lock:
        return {tid: [name for name, _c, _t in list(st)]
                for tid, st in _open_stacks.items() if st}


# ---------------------------------------------------------------------------
# per-step phase breakdown
# ---------------------------------------------------------------------------

def step_table(recs=None):
    """Pivot span records into {step: {phase: seconds}}.

    Only the OUTERMOST spans of each category are summed, per step and
    thread (a ``fwd`` span nested under another ``fwd`` span would
    double-count its parent's time; ``trainer.create_states`` lies inside
    ``train_step.build``, both ``compile``).  Categories outside PHASES
    accumulate under ``other``; the span around a whole step
    (``STEP_CAT``) is left out, its children are in.
    Back-dated records (``xla.*``, ``startup.import``) are left out too:
    their seconds lie inside whatever span was open around them -- the
    stages of a step's program inside ``whole_step``'s ``fwd`` time -- so
    no second is counted twice in a step's row.
    """
    recs = [r for r in (records() if recs is None else recs)
            if not r.get("backdated") and r["cat"] != STEP_CAT]
    table = {}
    # one thread's spans nest properly: walking them by start, a span
    # that begins before the last counted span of its (step, category,
    # thread) has ended lies inside it
    covered_until = {}
    for r in sorted(recs, key=lambda r: (r["t0"], -r["dur"])):
        key = (r["step"], r["cat"], r["tid"])
        if r["t0"] < covered_until.get(key, float("-inf")):
            continue
        covered_until[key] = r["t0"] + r["dur"]
        phase = r["cat"] if r["cat"] in PHASES else "other"
        row = table.setdefault(r["step"], {})
        row[phase] = row.get(phase, 0.0) + r["dur"]
    return table


def format_step_table(recs=None):
    """The per-step breakdown as a fixed-width text table (milliseconds)."""
    table = step_table(recs)
    cols = list(PHASES) + ["other"]
    lines = [f"{'step':>6}" + "".join(f"{c:>12}" for c in cols)
             + f"{'total':>12}"]
    for step in sorted(table):
        row = table[step]
        total = sum(row.values())
        lines.append(
            f"{step:>6}"
            + "".join(f"{row.get(c, 0.0) * 1e3:>12.3f}" for c in cols)
            + f"{total * 1e3:>12.3f}")
    if len(lines) == 1:
        lines.append("  (no spans recorded)")
    return "\n".join(lines)
