"""Device: time per traced step of the operations under the program's
``mla.rope`` scope, nested in ``mla`` — what latent attention runs between
its projections and its flash kernels: the rotary part of the queries and
of the one shared key, that key given to every head, and the move of q, k
and v to the kernels' (B, H, S, ..) layout; forward, recomputed forward
and backward together.  It reads a program that does this with separate
XLA ops and one that does it in the fused kernels alike, so it says which
part of ``device_mla_ms.train`` a change to the assembly moved."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/mla.rope/" in s) or None
