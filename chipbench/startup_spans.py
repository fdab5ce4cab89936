"""The program's start-up timeline, for the set-up readers.

One source, written by ``mxnet_tpu`` and not by the benchmark: the span
ring (``mxnet_tpu.diagnostics.spans.records()``), which since PR 41 holds,
on ``time.perf_counter()``'s clock,

* the spans of set-up's own work: ``startup.import``, ``startup.backend``,
  ``block.initialize``, ``amp.convert``, ``trainer.create_states``,
  ``train_step.build``, every ``train_step`` of the warm-up with its
  ``whole_step`` call and ``train_step.compile_capture``;
* one back-dated record per program and stage that JAX traced, lowered,
  loaded or built: ``xla.trace`` / ``xla.lower`` / ``xla.backend`` /
  ``xla.cache_load``, the program's name as ``fun``, ``how`` = ``loaded``
  or ``built`` on the backend record.  A program traced inside another's
  trace lies inside its parent's interval, so whatever spans programs is
  an interval union.

Set-up ends where the window's first ``train_step`` record begins (the
ring's last ``run["steps"]`` such records are the window's, as
``program_spans.ring`` reckons) and began ``run["setup_s"]`` before that;
what the plain reference compiles after the window is left out.

Readers run in the driver's process after the run.  A program "has the
records" when a ``startup.import`` record is on the ring; on one that has
not (the parent of the PR that added them, which has ``train_step`` spans
too) ``of_setup`` returns None, every reader returns None, none raises.
"""
import trace_reduce

STEP_FUN = "whole_step"
STATE_SPANS = ("block.initialize", "amp.convert", "trainer.create_states",
               "train_step.build")
_PREFIXES = ("startup.", "trainer.", "train_step", "xla.")
_NAMES = ("block.initialize", "amp.convert")


def of_setup(run):
    """(records of the timeline that lie in set-up, its first instant,
    its last), or None on a program without the records."""
    from mxnet_tpu.diagnostics import spans

    recs = spans.records()
    if not any(r.get("name") == "startup.import" for r in recs):
        return None
    n = int(run.get("steps") or 0)
    steps = [r for r in recs if r["name"] == "train_step"]
    if not n or len(steps) < n or not run.get("setup_s"):
        return None
    end = steps[-n]["t0"]
    begin = end - float(run["setup_s"])
    kept = [r for r in recs if r["t0"] < end and r["t0"] + r["dur"] > begin
            and (r["name"].startswith(_PREFIXES) or r["name"] in _NAMES)]
    return kept, begin, end


def _ival(r):
    return (r["t0"], r["t0"] + r["dur"])


def union_s(recs, lo=None, hi=None):
    """Seconds covered by the records' intervals, cut to [lo, hi]."""
    ivals = ((s if lo is None else max(s, lo), e if hi is None else min(e, hi))
             for s, e in map(_ival, recs))
    return sum(e - s for s, e in trace_reduce.union(ivals))


def span_s(run, names, union=False):
    """Seconds of the spans ``names`` over set-up, summed (or as an
    interval union); None where none of them was recorded."""
    got = of_setup(run)
    if got is None:
        return None
    recs = [r for r in got[0] if r["name"] in names]
    if not recs:
        return None
    return union_s(recs) if union else sum(r["dur"] for r in recs)


def step_stage(run, stage):
    """The ``xla.<stage>`` records of the whole-step program in set-up;
    None on a program without the records."""
    got = of_setup(run)
    if got is None:
        return None
    return [r for r in got[0]
            if r["name"] == "xla." + stage and r.get("fun") == STEP_FUN]


def step_stage_s(run, stage):
    """Their seconds, summed; None where there is none."""
    recs = step_stage(run, stage)
    return sum(r["dur"] for r in recs) if recs else None


def other_programs(run):
    """Every ``xla.*`` record of set-up that is not the whole-step
    program's and does not lie inside one of the whole-step program's (a
    kernel function traced inside the step's trace does); None on a
    program without the records."""
    got = of_setup(run)
    if got is None:
        return None
    xla = [r for r in got[0] if r["name"].startswith("xla.")]
    cover = trace_reduce.union(
        _ival(r) for r in xla if r.get("fun") == STEP_FUN)

    def inside(r):      # by its midpoint: a back-dated start is not exact
        mid = r["t0"] + r["dur"] / 2
        return any(s <= mid <= e for s, e in cover)

    return [r for r in xla if r.get("fun") != STEP_FUN and not inside(r)]
