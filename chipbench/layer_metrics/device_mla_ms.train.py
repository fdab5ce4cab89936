"""Device: time per traced step of the operations under the program's
``mla`` scope — latent attention whole: the query projection, the latent's
down projection, norm and up projection, the rotary part and the assembly
of the heads, the flash kernels (scope ``attention``, nested in it) and
the output projection; forward, recomputed forward and backward
together."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/mla/" in s) or None
