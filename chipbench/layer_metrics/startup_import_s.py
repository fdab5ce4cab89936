"""Compile: the program's span ``startup.import`` -- ``import mxnet_tpu``
from the first line of the package to its last, JAX's own import
included unless the caller had imported it before."""
import startup_spans


def read(trace, run):
    return startup_spans.span_s(run, ("startup.import",))
