"""Span-based step tracer: nested, thread-safe host spans in a ring buffer.

The telemetry registry (ISSUE 1) answers "how many / how much"; spans
answer "WHERE did this step's milliseconds go". Every instrumented layer
wraps its hot region in ``diagnostics.span(name, cat=phase)``:

  * gluon/trainer.py     step / collective(allreduce) / optimizer phases
  * gluon/block.py       the CachedOp call path (``fwd`` phase, compile)
  * autograd.backward    the ``bwd`` phase
  * engine.py            waitall / wait_to_read (``sync`` phase)
  * kvstore + parallel   collective dispatch (``collective`` phase)
  * gluon/data loader    batch fetch (``data`` phase)

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
    "span", "enabled", "enable", "disable", "reset",
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
    span's name (``parent``) and the current step index; the profiler
    annotation (module docstring) carries ``kv`` as event stats.
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
        if _trace_ctx:
            rec.update(_trace_ctx)
        with _ring_lock:
            _ring.append(rec)
        return False


def records():
    """Snapshot of the ring, oldest first."""
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

    Only depth-0 spans of each category are summed (a ``fwd`` span nested
    under another ``fwd`` span would double-count its parent's time).
    Categories outside PHASES accumulate under ``other``; the span
    around a whole step (``STEP_CAT``) is left out, its children are in.
    """
    recs = records() if recs is None else recs
    # innermost-per-category: keep a span unless an enclosing span of the
    # SAME category covers it (nested fwd under fwd); cheap approximation:
    # group by (step, cat) over minimum depth seen for that pair
    min_depth = {}
    for r in recs:
        key = (r["step"], r["cat"], r["tid"])
        d = min_depth.get(key)
        if d is None or r["depth"] < d:
            min_depth[key] = r["depth"]
    table = {}
    for r in recs:
        if r["cat"] == STEP_CAT or \
                r["depth"] != min_depth[(r["step"], r["cat"], r["tid"])]:
            continue
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
