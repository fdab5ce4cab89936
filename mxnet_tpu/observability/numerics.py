"""In-graph numerics checks + the NaN-bisect interpreter.

The reference stack's ``monitor.py`` watched per-op tensor stats through
executor callbacks; a jit'd program has no callback seam, so the checks
must live IN the compiled program. :class:`NumericsPass` is a graph pass
(PR-7 pipeline, ``kind in (block, whole_step)``) driven by
``MXTPU_NUMERICS``:

  * ``step`` — one fused is-finite scalar per program: every inexact
    output (for the whole-step program: the loss, the updated params,
    the new optimizer state, the BN aux — grads feed all of them) is
    AND-reduced into a single bool delivered through an async
    ``jax.debug.callback``. Cost per dispatch: one reduction fused into
    the program, zero extra host syncs (the device pushes the byte when
    the step completes; ``gluon.TrainStep`` reads the verdict at its
    step-boundary sync).
  * ``op`` — a per-equation flag vector: the program is re-emitted
    equation by equation (``subgraph._eval_eqn``), each inexact-output
    equation contributes one is-finite bit, and ONE callback carries the
    stacked vector. A trip is attributed immediately from the rewrite-
    time equation table (op name / shapes / dtypes) with no re-run —
    the always-on debugging mode.

On a tripped ``step`` check the owner re-runs the recorded program
through :func:`bisect` — an eager, eqn-by-eqn walk reusing
``subgraph._eval_eqn`` that descends into jit/remat/custom-call bodies
and stops at the FIRST equation producing a non-finite value, reporting
op name, output shapes/dtypes, which operand was already non-finite,
and per-operand stats. The report lands in the postmortem bundle
(docs/observability.md).
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

from ..passes.manager import GraphPass, retrace_flat

__all__ = [
    "NumericsPass", "NonFiniteError", "mode", "normalize", "bisect",
    "bisect_callable", "tripped", "take_trip", "trips", "reset",
    "effects_barrier",
]

MODES = ("off", "step", "op")

_trip_lock = threading.Lock()
_trips = []          # oldest-first trip dicts (bounded below)
_MAX_TRIPS = 64
_programs = {}       # pid -> {"label", "mode", "checks", "table"}
_next_pid = [0]


class NonFiniteError(ArithmeticError):
    """A numerics check tripped. ``.trip`` is the flight-recorder trip
    record, ``.report`` the bisect attribution (may be None when the
    re-run could not reproduce it), ``.bundle`` the postmortem path."""

    def __init__(self, message, trip=None, report=None, bundle=None):
        super().__init__(message)
        self.trip = trip
        self.report = report
        self.bundle = bundle


def normalize(raw):
    """MXTPU_NUMERICS value -> off|step|op. Unrecognized spellings
    ('none', '1', 'true', typos) resolve to 'off': pass installation
    (passes/manager.resolve_passes) and the step-boundary poll
    (gluon.TrainStep) share THIS function, so a value that installs no
    NumericsPass must not make TrainStep disable donation and pay the
    effects barrier for checks that never run."""
    m = str(raw).strip().lower()
    return m if m in MODES else "off"


def mode():
    """Live MXTPU_NUMERICS value, normalized to off|step|op."""
    import os

    raw = None
    try:
        from .. import env as _env

        if "MXTPU_NUMERICS" in _env.all_vars():
            raw = _env.get("MXTPU_NUMERICS")
    except Exception:
        raw = None
    if raw is None:
        raw = os.environ.get("MXTPU_NUMERICS", "off")
    return normalize(raw)


def effects_barrier():
    """Wait for pending debug-callback deliveries (the verdict for a
    dispatch is guaranteed in once the program AND its effects land)."""
    try:
        jax.effects_barrier()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# trip bookkeeping (callbacks land here, owners poll at sync points)
# ---------------------------------------------------------------------------


def _register_program(label, pmode, checks, table=None):
    pid = _next_pid[0]
    _next_pid[0] += 1
    _programs[pid] = {"label": label, "mode": pmode, "checks": checks,
                      "table": table or []}
    return pid


def _record_trip(pid, attribution=None):
    meta = _programs.get(pid, {"label": f"pid{pid}", "mode": "?"})
    trip = {"label": meta["label"], "mode": meta["mode"]}
    if attribution:
        trip["equation"] = attribution
    try:
        from ..diagnostics import spans as _spans

        trip["step"] = _spans.current_step()
    except Exception:
        trip["step"] = 0
    with _trip_lock:
        _trips.append(trip)
        del _trips[:-_MAX_TRIPS]
    try:
        from ..telemetry import instruments as _instr

        _instr.record_numerics_trip(meta["label"])
    except Exception:
        pass
    try:
        from . import flight

        flight.record("numerics_trip", **trip)
    except Exception:
        pass
    return trip


def _on_step_flag(pid, ok):
    if not bool(ok):
        _record_trip(pid)


def _on_op_flags(pid, flags):
    import numpy as onp

    flags = onp.asarray(flags).astype(bool)
    if flags.all():
        return
    meta = _programs.get(pid)
    idx = int(onp.argmax(~flags))
    attribution = None
    if meta and idx < len(meta["table"]):
        attribution = dict(meta["table"][idx])
    _record_trip(pid, attribution)


def tripped():
    with _trip_lock:
        return bool(_trips)


def trips():
    with _trip_lock:
        return list(_trips)


def take_trip(label_prefix=None):
    """Pop (and return) the oldest trip, optionally only one whose label
    starts with ``label_prefix``; None when nothing tripped."""
    with _trip_lock:
        for i, t in enumerate(_trips):
            if label_prefix is None or \
                    str(t.get("label", "")).startswith(label_prefix):
                return _trips.pop(i)
    return None


def reset():
    with _trip_lock:
        _trips.clear()


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


def _is_inexact_aval(aval):
    dt = getattr(aval, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jnp.inexact)


def _is_dropvar(v):
    return type(v).__name__ == "DropVar"


class NumericsPass(GraphPass):
    """MXTPU_NUMERICS in-graph is-finite instrumentation (step | op)."""

    name = "numerics"
    priority = 99  # after AMP/remat: instrument the program that RUNS
    kinds = ("block", "whole_step")

    def __init__(self, mode_=None):
        self._mode = mode_

    def effective_mode(self):
        m = (self._mode or mode()).strip().lower()
        return m if m in ("step", "op") else ("off" if m in (
            "", "0", "off", "false", "no") else "step")

    def applies(self, ctx):
        return super().applies(ctx) and self.effective_mode() != "off"

    def run(self, closed, ctx):
        m = self.effective_mode()
        label = f"{ctx.label}/{ctx.variant or ctx.kind}"
        if m == "op":
            fn = _instrument_per_eqn(closed, label)
        else:
            fn = _instrument_outputs(closed, label)
        if fn is None:  # nothing inexact to check: keep the program
            return closed
        return retrace_flat(fn, closed)


def _instrument_outputs(closed, label):
    """step mode: AND-reduce isfinite over every inexact program output
    into one scalar, delivered asynchronously."""
    n_checked = sum(1 for v in closed.jaxpr.outvars
                    if _is_inexact_aval(getattr(v, "aval", None)))
    if not n_checked:
        return None
    pid = _register_program(label, "step", n_checked)

    def fn(*flat):
        outs = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *flat)
        checks = [jnp.isfinite(o).all() for o in outs
                  if jnp.issubdtype(jnp.result_type(o), jnp.inexact)]
        ok = functools.reduce(jnp.logical_and, checks)
        jax.debug.callback(functools.partial(_on_step_flag, pid), ok)
        return tuple(outs)

    return fn


def _eqn_meta(index, eqn, path=""):
    return {
        "eqn": f"{path}{index}",
        "op": eqn.primitive.name,
        "out_shapes": [tuple(getattr(v.aval, "shape", ()))
                       for v in eqn.outvars if not _is_dropvar(v)],
        "out_dtypes": [str(getattr(v.aval, "dtype", "?"))
                       for v in eqn.outvars if not _is_dropvar(v)],
        "in_shapes": [tuple(getattr(getattr(v, "aval", None), "shape", ()))
                      for v in eqn.invars],
        "in_dtypes": [str(getattr(getattr(v, "aval", None), "dtype", "?"))
                      for v in eqn.invars],
    }


def _instrument_per_eqn(closed, label):
    """op mode: the program re-emitted eqn by eqn with one is-finite bit
    per inexact-output equation; one callback carries the stacked
    vector, and a trip is attributed from the static equation table."""
    from ..subgraph import _eval_eqn
    from jax.extend import core as jcore

    jaxpr = closed.jaxpr
    table = []
    checked = {}
    for i, eqn in enumerate(jaxpr.eqns):
        if any(_is_inexact_aval(getattr(v, "aval", None))
               for v in eqn.outvars if not _is_dropvar(v)):
            checked[i] = len(table)
            table.append(_eqn_meta(i, eqn))
    if not table:
        return None
    pid = _register_program(label, "op", len(table), table)

    def fn(*flat):
        env = {}

        def read(v):
            if isinstance(v, jcore.Literal):
                return v.val
            return env[v]

        for v, c in zip(jaxpr.constvars, closed.consts):
            env[v] = c
        for v, a in zip(jaxpr.invars, flat):
            env[v] = a
        flags = []
        for i, eqn in enumerate(jaxpr.eqns):
            out = _eval_eqn(eqn, [read(v) for v in eqn.invars])
            if not isinstance(out, (tuple, list)):
                out = [out]
            for v, val in zip(eqn.outvars, out):
                env[v] = val
            if i in checked:
                bits = [jnp.isfinite(val).all()
                        for v, val in zip(eqn.outvars, out)
                        if not _is_dropvar(v)
                        and _is_inexact_aval(getattr(v, "aval", None))]
                flags.append(functools.reduce(jnp.logical_and, bits))
        jax.debug.callback(functools.partial(_on_op_flags, pid),
                           jnp.stack(flags))
        return [read(v) for v in jaxpr.outvars]

    return fn


# ---------------------------------------------------------------------------
# the bisect interpreter (postmortem attribution for step mode)
# ---------------------------------------------------------------------------

_CALL_PRIMS = ("jit", "closed_call", "remat2", "checkpoint")
_CUSTOM_PRIMS = ("custom_jvp_call", "custom_vjp_call")


def _operand_stats(x):
    """Small host summary of one operand (device reductions, then tiny
    scalars to host — this only runs during a postmortem)."""
    try:
        xa = jnp.asarray(x)
        if not jnp.issubdtype(xa.dtype, jnp.inexact):
            return {"shape": tuple(xa.shape), "dtype": str(xa.dtype),
                    "finite_frac": 1.0}
        xf = xa.astype(jnp.float32)
        finite = jnp.isfinite(xf)
        return {
            "shape": tuple(xa.shape), "dtype": str(xa.dtype),
            "finite_frac": float(finite.mean()),
            "nan_count": int(jnp.isnan(xf).sum()),
            "inf_count": int(jnp.isinf(xf).sum()),
            "min": float(jnp.nanmin(jnp.where(finite, xf, jnp.nan))),
            "max": float(jnp.nanmax(jnp.where(finite, xf, jnp.nan))),
        }
    except Exception as e:  # stats must never mask the attribution
        return {"error": repr(e)}


def _inner_closed(eqn):
    """The inner ClosedJaxpr of a call-like equation, or None."""
    from jax.extend import core as jcore

    p = eqn.params
    name = eqn.primitive.name
    if name in ("jit", "closed_call"):
        return p.get("jaxpr")
    if name in ("remat2", "checkpoint"):
        inner = p.get("jaxpr")
        if inner is not None and not hasattr(inner, "consts"):
            return jcore.ClosedJaxpr(inner, ())
        return inner
    for key in ("call_jaxpr", "fun_jaxpr"):
        inner = p.get(key)
        if inner is not None:
            if not hasattr(inner, "consts"):
                return jcore.ClosedJaxpr(inner, ())
            return inner
    return None


def _walk(jaxpr, consts, args, path, out):
    """Eager eqn-by-eqn eval; fills ``out`` with the first non-finite
    equation's report and returns the eqn outputs for the caller."""
    from jax.extend import core as jcore

    from ..subgraph import _eval_eqn

    env = {}

    def read(v):
        if isinstance(v, jcore.Literal):
            return v.val
        return env[v]

    for v, c in zip(jaxpr.constvars, consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a
    for i, eqn in enumerate(jaxpr.eqns):
        invals = [read(v) for v in eqn.invars]
        vals = _eval_eqn(eqn, invals)
        if not isinstance(vals, (tuple, list)):
            vals = [vals]
        for v, val in zip(eqn.outvars, vals):
            env[v] = val
        if out:  # already attributed deeper in this walk
            continue
        bad = None
        for k, (v, val) in enumerate(zip(eqn.outvars, vals)):
            if _is_dropvar(v) or \
                    not _is_inexact_aval(getattr(v, "aval", None)):
                continue
            if not bool(jnp.isfinite(val).all()):
                bad = k
                break
        if bad is None:
            continue
        inner = _inner_closed(eqn)
        if inner is not None and len(inner.jaxpr.invars) == len(invals):
            try:
                _walk(inner.jaxpr, inner.consts, invals, f"{path}{i}/",
                      out)
            except Exception:
                pass  # misaligned body: attribute the call eqn itself
            if out:
                continue
        meta = _eqn_meta(i, eqn, path)
        meta["first_bad_output"] = bad
        meta["operands"] = [_operand_stats(x) for x in invals]
        meta["params"] = {k: str(v)[:120] for k, v in eqn.params.items()
                          if k not in ("jaxpr", "call_jaxpr", "fun_jaxpr")}
        out.append(meta)
    return [read(v) for v in jaxpr.outvars]


def bisect(closed, args):
    """Re-run ``closed`` eagerly on the recorded operands and return the
    first-non-finite-equation report (None when everything stayed
    finite — e.g. the operands were already consumed/donated)."""
    flat, _ = jax.tree_util.tree_flatten(args)
    if len(flat) != len(closed.jaxpr.invars):
        raise ValueError(
            f"bisect: {len(flat)} operands for a program with "
            f"{len(closed.jaxpr.invars)} inputs")
    out = []
    _walk(closed.jaxpr, closed.consts, flat, "", out)
    return out[0] if out else None


def bisect_callable(fn, *args):
    """Trace ``fn`` at ``args`` (side-effect-suppressed) and bisect the
    captured program on those exact operands."""
    from ..passes import _state as _pass_state

    with _pass_state.suppress_trace_bumps():
        closed = jax.make_jaxpr(fn)(*args)
    return bisect(closed, args)


def format_report(report):
    """One-line human rendering of a bisect/op-mode attribution."""
    if not report:
        return "(no attribution)"
    ops = ", ".join(
        f"op{j}[{o.get('dtype', '?')}{list(o.get('shape', ()))}"
        f" finite={o.get('finite_frac', '?')}]"
        for j, o in enumerate(report.get("operands", [])))
    return (f"eqn {report.get('eqn')} `{report.get('op')}` "
            f"out_shapes={report.get('out_shapes')} "
            f"out_dtypes={report.get('out_dtypes')}"
            + (f" operands: {ops}" if ops else ""))
