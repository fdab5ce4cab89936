"""Device: time per traced step of the operations under ``kda.scan``,
nested in ``kda`` — `npx.kda_scan` alone, forward, recomputed forward and
backward together: its two Pallas kernels and whatever XLA puts around
them (the padding, beta's transpose, the casts).  It reads a program that
runs the composition alike.  None on a program without the scope."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/kda.scan/" in s) or None
