"""Compile: seconds XLA spent building programs during set-up -- the
program's counter ``xla_compile_seconds_total{stage=backend}`` less
``{stage=cache_load}`` (JAX times a cache hit's load inside the backend
event), less what compiled after the last training step."""
import program_spans


def read(trace, run):
    c = program_spans.xla_compiles_of_setup()
    return None if c is None else c["backend_s"] - c["cache_load_s"]
