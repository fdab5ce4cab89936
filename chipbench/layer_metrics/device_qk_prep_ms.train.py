"""Device: time per traced step of what a grouped-query attention block
runs besides its flash kernels and its four projections — the per-head
norms, the rotary positions and every transpose between the projections'
layout and the kernels' (B, H, S, D), forward, recomputed forward and
backward together.  It reads the block's scope (``GroupedQueryAttention_*``
less ``attention`` and the ``Dense_*`` children), not an op's name, so it
reads a program that prepares queries and keys with separate XLA ops and
one that does it in one kernel alike."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/GroupedQueryAttention_" in s
        and "/attention/" not in s and "/Dense_" not in s)
