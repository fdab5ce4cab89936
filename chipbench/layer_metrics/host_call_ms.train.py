"""Entry points (gluon.TrainStep): host time of the compiled call itself,
the program's span ``whole_step`` (argument handling, the transfers of
the scalar operands, the enqueue); median over the window's steps, from
the program's span ring."""
import statistics

import program_spans


def read(trace, run):
    sums = program_spans.per_step_sum(program_spans.ring(run),
                                      ("whole_step",))
    return None if not sums else statistics.median(sums) * 1e3
