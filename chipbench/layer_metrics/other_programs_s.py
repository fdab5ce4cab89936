"""Compile: every program of set-up but the whole step -- the interval
union of the program's ``xla.*`` records whose ``fun`` is not
``whole_step`` and that do not lie inside one of ``whole_step``'s: casts,
initializer draws, optimizer state, the benchmark's own weights and
norms, traced, lowered and loaded or built."""
import startup_spans


def read(trace, run):
    recs = startup_spans.other_programs(run)
    return None if recs is None else startup_spans.union_s(recs)
