"""Device: time per traced step of the operations under the program's
``moe.shared`` scope — the shared expert that every token passes beside
the routed ones; forward, recomputed forward and backward together."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "/moe.shared/" in s) or None
