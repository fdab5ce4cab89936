"""Device: share of the traced operations' time that resolved to a scope
of the program (forward, backward, optimizer or grad_reduce) through the
compile registry's ``op_scopes``; the check on every other scope metric."""
import program_spans


def read(trace, run):
    scoped = program_spans.scope_seconds(
        trace, lambda s: program_spans.phase_of(s) is not None)
    total = sum((trace.get("op_s") or {}).values())
    return None if scoped is None or not total else 100.0 * scoped / total
