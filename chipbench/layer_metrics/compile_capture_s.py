"""Compile: the program's span ``train_step.compile_capture`` -- the
second ``lower`` and ``compile`` of the step, its optimized text and the
scope map parsed from it, inside the first call -- summed over set-up."""
import startup_spans


def read(trace, run):
    return startup_spans.span_s(run, ("train_step.compile_capture",))
