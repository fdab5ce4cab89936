"""Entry points (gluon.TrainStep): host time, per step, before the
compiled call -- the program's spans ``train_step.prologue`` (rescale,
per-parameter lr / wd / update count, hyper-parameters) and
``train_step.operands`` (gathering operands, donation check); median
over the window's steps, from the program's span ring."""
import statistics

import program_spans


def read(trace, run):
    sums = program_spans.per_step_sum(
        program_spans.ring(run),
        ("train_step.prologue", "train_step.operands"))
    return None if not sums else statistics.median(sums) * 1e3
