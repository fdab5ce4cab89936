"""Entry points (gluon.TrainStep): host time, per step, after the
compiled call -- the program's spans ``train_step.writeback`` (results
into the live parameters and optimizer state) and
``train_step.bookkeeping`` (the program's own counters, step index and
flight record); median over the window's steps, from the span ring."""
import statistics

import program_spans


def read(trace, run):
    sums = program_spans.per_step_sum(
        program_spans.ring(run),
        ("train_step.writeback", "train_step.bookkeeping"))
    return None if not sums else statistics.median(sums) * 1e3
