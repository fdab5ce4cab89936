"""Device: time per traced step of what the grouped-query attention
blocks of a conv-attention hybrid run besides their flash kernels and
their four projections — the per-head norms, the rotary positions and
every transpose between the projections' layout and the kernels'
(B, H, S, D), forward, recomputed forward and backward together.  The
block's scope exactly as ``device_qk_prep_ms.train`` reads it
(``GroupedQueryAttention_*`` less ``attention`` and the ``Dense_*``
children), so it reads a program that prepares 64-wide heads with separate
XLA ops and one that does it in the fused kernels alike.  None on a
configuration of another kind (`kernel_counts_hybrid.applies`)."""
import kernel_counts_hybrid
import program_spans


def read(trace, run):
    if not kernel_counts_hybrid.applies(run["cfg"]):
        return None
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/GroupedQueryAttention_" in s
        and "/attention/" not in s and "/Dense_" not in s)
