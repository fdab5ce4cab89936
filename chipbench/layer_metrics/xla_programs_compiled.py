"""Compile: programs XLA built (not loaded from the persistent cache)
during set-up -- the program's counter ``xla_programs_total{how=built}``
less what was built after the last training step."""
import program_spans


def read(trace, run):
    c = program_spans.xla_compiles_of_setup()
    return None if c is None else c["built"]
