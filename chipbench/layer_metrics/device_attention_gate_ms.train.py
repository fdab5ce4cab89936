"""Device: time per traced step of the operations under the program's
``attention.gate`` scope — the fifth projection of a gated attention
layer and the sigmoid that multiplies the heads' output before the output
projection; forward, recomputed forward and backward together.  None on a
program without the scope."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/attention.gate/" in s) or None
