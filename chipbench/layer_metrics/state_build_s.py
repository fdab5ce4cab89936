"""Compile: building the state before the first step -- the program's
spans ``block.initialize``, ``amp.convert`` (the offline cast of the
weights), ``trainer.create_states`` and ``train_step.build`` over set-up,
as an interval union: under a TrainStep the states are created inside the
build, and a sum would count them twice."""
import startup_spans


def read(trace, run):
    return startup_spans.span_s(run, startup_spans.STATE_SPANS, union=True)
