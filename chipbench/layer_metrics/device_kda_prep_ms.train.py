"""Device: time per traced step of the operations under ``kda.conv``,
``kda.gate`` and ``kda.out`` outside their matrix products — what Kimi
Delta Attention runs between its three projections and its scan, and
between the scan and the output projection: the taps with SiLU, the two
l2 norms, the decay's softplus and exponential, beta's and the gate's
sigmoids, the gated head norm; forward, recomputed forward and backward
together.  The low-rank pairs', beta's and the output projection's
products (scope ``Dense_*``) are left out, with whatever XLA fused into
them: the reader follows the scope of a fusion's root.  None on a program
without the scopes."""
import program_spans

SCOPES = ("/kda.conv/", "/kda.gate/", "/kda.out/")


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: any(p in s for p in SCOPES)
        and "/Dense_" not in s) or None
