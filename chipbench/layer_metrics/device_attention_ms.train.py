"""Device: time per traced step of the operations under the program's
``attention`` scope (the flash kernels and what XLA fused around them),
forward, recomputed forward and backward together."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/attention/" in s)
