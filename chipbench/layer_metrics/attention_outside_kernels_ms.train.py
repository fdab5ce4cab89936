"""Kernels: device time per traced step of the operations under the
program's ``attention`` scope that are NOT a Pallas/Mosaic call — what
XLA runs around the flash kernels (copies, reshapes and reductions over
their row statistics, transposes of their operands and results): the
scope's time as ``device_attention_ms.train`` reads it, less the kernels'
as the flash rooflines read it.  None on a program whose step holds no
operation under such a scope (a model without attention)."""
import kernel_counts
import program_spans


def _attention(scope):
    return "/attention/" in scope


def read(trace, run):
    scopes = program_spans.op_scopes() or {}
    if not any(map(_attention, scopes.values())):
        return None
    under = program_spans.per_traced_step_ms(trace, run, _attention)
    if under is None:
        return None
    kernels = kernel_counts.kernel_seconds(trace, scope_part="/attention/")
    return under - kernels * 1e3 / run["traced_steps"]
