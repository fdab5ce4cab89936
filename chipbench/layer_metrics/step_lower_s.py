"""Compile: lowering the whole-step program to MLIR -- the program's
records ``xla.lower`` with ``fun`` = ``whole_step``, summed over set-up;
a second lowering by the compile capture shows here."""
import startup_spans


def read(trace, run):
    return startup_spans.step_stage_s(run, "lower")
