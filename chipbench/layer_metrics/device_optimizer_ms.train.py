"""Device: time per traced step of the operations under the program's
``optimizer`` scope (the fused update of every bucket)."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: program_spans.phase_of(s) == "optimizer")
