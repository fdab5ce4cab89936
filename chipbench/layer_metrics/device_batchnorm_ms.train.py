"""Device: time per traced step of the operations whose innermost block
scope is a ``BatchNorm*`` block, forward and backward together.  A fusion
counts under the scope of its root, so this is the BatchNorm work XLA
left in fusions of its own: what it fused into a convolution's fusion
counts as that ``Conv2D``'s (PERF.md section 5)."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run,
        lambda s: (program_spans.block_of(s) or "").startswith("BatchNorm"))
