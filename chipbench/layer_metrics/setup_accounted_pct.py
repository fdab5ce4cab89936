"""Compile: the share of ``setup_s`` that the program's own timeline
names -- the interval union of every span and stage record of set-up
(``startup.*``, ``block.initialize``, ``amp.convert``, ``trainer.*``,
``train_step*``, ``xla.*``) over ``setup_s``.  What is left is the
interpreter's start, the take of the TPU client and the run of the
benchmark's own weights."""
import startup_spans


def read(trace, run):
    got = startup_spans.of_setup(run)
    if got is None:
        return None
    recs, begin, end = got
    return 100.0 * startup_spans.union_s(recs, begin, end) / (end - begin)
