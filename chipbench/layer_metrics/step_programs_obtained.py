"""Compile: how often set-up obtained an executable for the whole-step
program -- the program's records ``xla.backend`` with ``fun`` =
``whole_step``.  1 is sound; 2 means the compile capture fetched it
again."""
import startup_spans


def read(trace, run):
    recs = startup_spans.step_stage(run, "backend")
    return len(recs) if recs else None
