"""Device: time per traced step of the operations under the program's
``kda`` scope — Kimi Delta Attention whole: the three projections, the
taps with SiLU and the two l2 norms, the decay, beta and the output gate's
pair, the scan's kernels, the gated head norm and the output projection;
forward, recomputed forward and backward together.  None on a program
without the scope."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/kda/" in s) or None
