"""Device: time per traced step of the operations under the program's
``short_conv`` scope — the gated short convolution whole: the projection
to three streams, the mix (scope ``short_conv.mix``, nested in it) and
the output projection; forward, recomputed forward and backward together.
None on a program without the scope."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/short_conv/" in s) or None
