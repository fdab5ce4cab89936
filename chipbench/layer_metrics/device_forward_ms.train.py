"""Device: time per traced step of the operations the program scoped as
forward -- ``jvp(forward)`` or ``jvp(loss)`` and no ``transpose(`` in the
instruction's op_name (the compile registry's ``op_scopes``)."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: program_spans.phase_of(s) == "forward")
