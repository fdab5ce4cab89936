"""The start-up report: where the seconds before the first finished step
went, reduced from the span ring (docs/diagnostics.md, "The spans of
start-up").

The ring holds, on one clock, the spans of set-up's own work
(``startup.import``, ``startup.backend``, ``block.initialize``,
``amp.convert``, ``trainer.create_states``, ``train_step.build``, the
first ``train_step`` with its ``whole_step`` call and
``train_step.compile_capture``) and one back-dated record per program and
stage that JAX traced, lowered, loaded or built (``xla.trace`` /
``xla.lower`` / ``xla.backend`` / ``xla.cache_load``, the program's name
as ``fun``).  :func:`reduce` turns them into a dict of a few dozen
numbers.  ``TrainStep`` calls :func:`take` when a step compiled, so a run
that has long rolled its ring still has the answer, and a later retrace
shows as a further entry; :func:`startup_report` returns it.

Nested programs (a jitted kernel function traced inside the step's trace)
record themselves inside their parent's interval, so sums are taken per
program and everything that spans programs is an interval union.
"""
from __future__ import annotations

import os
import threading

from . import spans

__all__ = ["startup_report", "format_startup_table", "cache_contents",
           "reduce", "take", "reset", "STEP_FUN"]

# the name JAX knows the whole-step program by (gluon/train_step.py)
STEP_FUN = "whole_step"
STATE_SPANS = ("block.initialize", "amp.convert", "trainer.create_states",
               "train_step.build")
CAPTURE_SPANS = ("compile_capture.lower", "compile_capture.compile",
                 "compile_capture.text", "compile_capture.op_scopes")
_STAGES = ("trace", "lower", "backend", "cache_load")
_KEPT = 24      # programs / cache modules listed by name; the rest summed
_MAX_TAKEN = 16

_taken = []     # one reduction per step that compiled, oldest first
_lock = threading.Lock()


def _ival(r):
    return (r["t0"], r["t0"] + r["dur"])


def _union(intervals):
    """Merged, sorted (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _seconds(intervals):
    return sum(e - s for s, e in _union(intervals))


def _inside(r, covers):
    """Whether the record's midpoint lies in one of the merged intervals
    (a back-dated start is good to the clocks' agreement, not exact)."""
    mid = r["t0"] + r["dur"] / 2
    return any(s <= mid <= e for s, e in covers)


def _timeline(name):
    # "whole_step" here is the SPAN around the compiled call, which
    # TrainStep names after the function it calls
    return (name.startswith(("startup.", "trainer.", "train_step", "xla.",
                             "compile_capture."))
            or name in ("block.initialize", "amp.convert", "whole_step"))


def reduce(recs, since=None):
    """The start-up numbers of ``recs`` (ring records); ``since`` keeps
    only what began at or after that instant (a later compile's own
    share)."""
    recs = [r for r in recs if _timeline(r["name"])
            and (since is None or r["t0"] >= since)]
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)

    def total(name):
        return sum(r["dur"] for r in by_name.get(name, ()))

    xla = [r for r in recs if r["name"].startswith("xla.")]
    step = [r for r in xla if r.get("fun") == STEP_FUN]
    step_cover = _union(_ival(r) for r in step)
    other = [r for r in xla if r.get("fun") != STEP_FUN
             and not _inside(r, step_cover)]
    xla_cover = _union(_ival(r) for r in xla)
    # the calls that obtained the step: what is left of them once the
    # stages are taken out is the first run (and the call's own overhead)
    first_run = 0.0
    for r in by_name.get("whole_step", ()):
        if any(_inside(s, [_ival(r)]) for s in step):
            a, b = _ival(r)
            first_run += r["dur"] - sum(
                max(0.0, min(b, e) - max(a, s)) for s, e in xla_cover)

    def stage(rs, name):
        return sum(r["dur"] for r in rs if r["name"] == "xla." + name)

    seconds = {
        "import": total("startup.import"),
        "backend": total("startup.backend"),
        "block_initialize": total("block.initialize"),
        "amp_convert": total("amp.convert"),
        "create_states": total("trainer.create_states"),
        "step_build": total("train_step.build"),
        # create_states runs inside step_build under a TrainStep: a union
        "state_build": _seconds(_ival(r) for n in STATE_SPANS
                                for r in by_name.get(n, ())),
        **{"step_" + s: stage(step, s) for s in _STAGES},
        "other_programs": _seconds(_ival(r) for r in other),
        "compile_capture": total("train_step.compile_capture"),
        **{n.replace("compile_capture.", "capture_"): total(n)
           for n in CAPTURE_SPANS},
        "first_run": first_run,
        "accounted": _seconds(_ival(r) for r in recs),
    }
    backends = [r for r in step if r["name"] == "xla.backend"]
    outside = {id(r) for r in other}
    programs = {}
    for r in xla:
        fun = r.get("fun")
        p = programs.setdefault(fun, {
            "fun": fun, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache_load_s": 0.0, "obtained": 0, "how": None,
            "in_step": fun != STEP_FUN})
        p[r["name"][4:] + "_s"] += r["dur"]
        if r["name"] == "xla.backend":
            p["obtained"] += 1
            p["how"] = r.get("how")
        if id(r) in outside:
            p["in_step"] = False
    ranked = sorted(programs.values(),
                    key=lambda p: -(p["trace_s"] + p["lower_s"]
                                    + p["backend_s"]))
    rest = ranked[_KEPT:]
    return {
        "seconds": seconds,
        "step": {
            "fun": STEP_FUN,
            "traced": sum(r["name"] == "xla.trace" for r in step),
            "lowered": sum(r["name"] == "xla.lower" for r in step),
            "obtained": len(backends),
            "loaded": sum(r.get("how") == "loaded" for r in backends),
            "built": sum(r.get("how") == "built" for r in backends),
        },
        "other_programs": sum(r["name"] == "xla.backend" for r in other),
        "programs": ranked[:_KEPT],
        "programs_left_out": {
            "count": len(rest),
            "seconds": sum(p["trace_s"] + p["lower_s"] + p["backend_s"]
                           for p in rest)},
        "records": len(recs),
        "from": min((r["t0"] for r in recs), default=None),
        "until": max((r["t0"] + r["dur"] for r in recs), default=None),
    }


def take():
    """Reduce the ring now and keep the result (TrainStep, at the end of a
    step that compiled: never on a warm step).  The first entry is the
    start-up; each later one covers what began since the one before."""
    if not spans.enabled():
        return
    recs = spans.records()
    with _lock:
        entry = reduce(recs, _taken[-1]["until"] if _taken else None)
        if len(_taken) >= _MAX_TAKEN:
            del _taken[1]       # the start-up stays, the oldest retrace goes
        _taken.append(entry)


def startup_report(cache=False):
    """Where the time to the first finished step went.

    Keys: ``seconds`` (by phase: ``import``, ``backend``,
    ``block_initialize``, ``amp_convert``, ``create_states``,
    ``step_build``, their union ``state_build``; ``step_trace`` /
    ``step_lower`` / ``step_backend`` / ``step_cache_load`` for the
    whole-step program; ``other_programs``, the interval union of every
    other program's stages outside the step's own; ``compile_capture``
    and its ``capture_lower`` / ``_compile`` / ``_text`` / ``_op_scopes``;
    ``first_run``, the compiled call less the stages inside it;
    ``accounted``, the union of all of it), ``step`` (how often the
    whole-step program was ``traced`` / ``lowered`` / ``obtained``, and
    ``loaded`` from the cache or ``built``), ``other_programs`` (their
    count), ``programs`` (by name, stage seconds, ``obtained``, ``how``,
    ``in_step`` for one traced only inside the step's trace; the 24 that
    took longest, the others summed under ``programs_left_out``),
    ``records`` / ``from`` / ``until`` (what was reduced, on
    ``perf_counter``'s clock), ``recompiles`` (the same dict for each
    LATER step that compiled: a retrace) and, with ``cache=True``,
    ``cache`` (:func:`cache_contents`; the directory is read only then).

    The numbers are those kept when the first step compiled
    (:func:`take`); before any did, the ring as it is now."""
    with _lock:
        entries = list(_taken)
    if not entries:
        entries = [reduce(spans.records())]
    out = dict(entries[0], recompiles=entries[1:])
    if cache:
        out["cache"] = cache_contents()
    return out


def cache_contents(path=None):
    """What fills the persistent compile cache: entries and bytes by
    module name (a cache file is ``<module>-<key>-cache``), largest first;
    None without a directory.  Default: the directory JAX uses
    (``$JAX_COMPILATION_CACHE_DIR``, else the package's ``.jax_cache``)."""
    if path is None:
        import jax

        path = jax.config.jax_compilation_cache_dir
    if not path or not os.path.isdir(path):
        return None
    modules = {}
    for entry in os.scandir(path):
        if not entry.name.endswith("-cache") or not entry.is_file():
            continue
        module = entry.name[:-len("-cache")].rpartition("-")[0]
        m = modules.setdefault(module, {"module": module, "entries": 0,
                                        "bytes": 0})
        m["entries"] += 1
        m["bytes"] += entry.stat().st_size
    ranked = sorted(modules.values(), key=lambda m: -m["bytes"])
    rest = ranked[_KEPT:]
    return {
        "dir": path,
        "entries": sum(m["entries"] for m in ranked),
        "bytes": sum(m["bytes"] for m in ranked),
        "modules": ranked[:_KEPT],
        "modules_left_out": {"count": len(rest),
                             "entries": sum(m["entries"] for m in rest),
                             "bytes": sum(m["bytes"] for m in rest)},
    }


def format_startup_table(report=None):
    """The start-up report as text (seconds)."""
    rep = startup_report() if report is None else report
    if not rep["records"]:
        return "  (no start-up records on the ring)"
    sec, st = rep["seconds"], rep["step"]
    lines = [f"{'phase':<28}{'seconds':>10}"]
    for key, label in (
            ("import", "import mxnet_tpu"),
            ("backend", "first device resolution"),
            ("state_build", "state build (union)"),
            ("block_initialize", "  block.initialize"),
            ("amp_convert", "  amp.convert"),
            ("step_build", "  train_step.build"),
            ("create_states", "    trainer.create_states"),
            ("step_trace", f"{STEP_FUN}: trace"),
            ("step_lower", f"{STEP_FUN}: lower"),
            ("step_backend", f"{STEP_FUN}: backend"),
            ("step_cache_load", "  of which cache load"),
            ("first_run", f"{STEP_FUN}: first run"),
            ("compile_capture", "compile capture"),
            ("capture_lower", "  lower"),
            ("capture_compile", "  compile"),
            ("capture_text", "  text"),
            ("capture_op_scopes", "  op_scopes"),
            ("other_programs", f"other programs ({rep['other_programs']})"),
            ("accounted", "accounted (union)")):
        lines.append(f"{label:<28}{sec[key]:>10.3f}")
    lines.append(
        f"{STEP_FUN} traced {st['traced']}x, lowered {st['lowered']}x, "
        f"obtained {st['obtained']}x ({st['loaded']} loaded, "
        f"{st['built']} built); {rep['records']} records; "
        f"{len(rep['recompiles'])} later compile(s)")
    lines.append(f"{'program':<34}{'trace':>8}{'lower':>8}{'backend':>9}"
                 f"{'load':>8}  how")
    for p in rep["programs"]:
        how = f"{p['how'] or '-'} x{p['obtained']}" \
            + (" (in step)" if p["in_step"] else "")
        lines.append(f"{str(p['fun'])[:33]:<34}{p['trace_s']:>8.3f}"
                     f"{p['lower_s']:>8.3f}{p['backend_s']:>9.3f}"
                     f"{p['cache_load_s']:>8.3f}  {how}")
    left = rep["programs_left_out"]
    if left["count"]:
        lines.append(f"... and {left['count']} more, "
                     f"{left['seconds']:.3f} s together")
    cache = rep.get("cache")
    if cache:
        lines.append(f"compile cache {cache['dir']}: {cache['entries']} "
                     f"entries, {cache['bytes'] / 1e6:.1f} MB")
        for m in cache["modules"]:
            lines.append(f"  {m['module'][:40]:<41}{m['entries']:>6}"
                         f"{m['bytes'] / 1e6:>10.2f} MB")
        left = cache["modules_left_out"]
        if left["count"]:
            lines.append(f"  ... and {left['count']} more modules, "
                         f"{left['entries']} entries, "
                         f"{left['bytes'] / 1e6:.2f} MB")
    return "\n".join(lines)


def reset():
    """Forget the kept reductions (tests)."""
    with _lock:
        _taken.clear()
