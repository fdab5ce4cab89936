"""Compile: tracing the whole-step program -- the program's records
``xla.trace`` with ``fun`` = ``whole_step``, summed over set-up (the
kernel functions traced inside it are inside these seconds)."""
import startup_spans


def read(trace, run):
    return startup_spans.step_stage_s(run, "trace")
