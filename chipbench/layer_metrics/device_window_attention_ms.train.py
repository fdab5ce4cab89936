"""Device: time per traced step of the operations under the program's
``attention.window`` scope — the flash kernels of the layers that slide a
window (forward, the backward's recomputation where there is any, and the
backward), which the zoo's `GroupedQueryAttention` opens inside its
``attention`` scope in a stack whose layers differ in mask.  None on a
program without the scope."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/attention.window/" in s) or None
